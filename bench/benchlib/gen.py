"""Load generators: one general generator per kind of traffic file.

Serving (``"kind": "serve"``): open-loop arrivals of recommendation
requests, the logic of the program's ``serving/traffic.py`` copied here so
that the yardstick cannot move with the program.  A user's history prompt
is a per-user token stream plus a fresh suffix, so the histories of
returning users recur; users, prompt lengths and candidate items are
Zipf-distributed.

Every seed gets the same multiset of sizes and inter-arrival gaps, drawn
once from ``shape_seed`` in the traffic file, in an order of its own: the
seed changes which request comes when and what its tokens are, never how
much work the window holds.

Training (``"kind": "train"``): packed rows of item-token documents with
heavy-tailed lengths, every row different, drawn from the seed.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Tuple

import numpy as np

GREEDY = 0.0


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    rid: int
    user_id: int
    prompt: Tuple[int, ...]
    max_new_tokens: int
    due: float                        # seconds after the window opens
    interactive: bool
    candidates: Optional[Tuple[int, ...]]


def _zipf(rng, a: float, lo: int, hi: int, size: int) -> np.ndarray:
    return np.clip(lo - 1 + rng.zipf(a, size=size), lo, hi)


def _rng(*key) -> np.random.Generator:
    return np.random.default_rng([int(k) & 0xFFFFFFFF for k in key]
                                 + [int(k) >> 32 for k in key])


def serve_shapes(mix: dict, n: int):
    """The seed-independent multiset: (user, prompt length, new tokens,
    interactive) per request, and n inter-arrival gaps summing to
    ``n / rate`` seconds."""
    rng = _rng(mix["shape_seed"], n)
    users = _zipf(rng, mix["zipf_users"], 1, mix["n_users"], n) - 1
    lengths = _zipf(rng, mix["zipf_prompt"], mix["prompt_min"],
                    mix["prompt_max"], n)
    new = rng.integers(mix["new_tokens_min"], mix["new_tokens_max"] + 1,
                       size=n)
    inter = rng.random(n) < mix["interactive_fraction"]
    gaps = rng.exponential(1.0, size=n)
    gaps *= (n / mix["rate"]) / gaps.sum()
    return users, lengths, new, inter, gaps


def _history(seed: int, user: int, length: int, vocab: int) -> np.ndarray:
    return _rng(seed, 0x4157, user).integers(3, vocab, size=length)


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   n_items: int, rate: Optional[float] = None,
                   first_rid: int = 0, start: float = 0.0
                   ) -> List[ServeRequest]:
    """The requests due in ``[start, start + seconds)``: ``rate * seconds``
    of them, the shapes of :func:`serve_shapes` in a seed's order."""
    if rate is not None:
        mix = {**mix, "rate": rate}
    n = max(1, int(round(mix["rate"] * seconds)))
    users, lengths, new, inter, gaps = serve_shapes(mix, n)
    rng = _rng(seed, 0x5E7, n, first_rid)
    order = rng.permutation(n)
    gaps = rng.permutation(gaps)
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    hist_max = mix["prompt_max"]
    out = []
    for i in range(n):
        j = order[i]
        u, length = int(users[j]), int(lengths[j])
        fresh = max(1, length // 4)
        hist = _history(seed, u, hist_max, vocab)[:length - fresh]
        suffix = rng.integers(3, vocab, size=fresh)
        prompt = tuple(int(t) for t in np.concatenate([hist, suffix]))
        cands = None
        if mix["candidates"]:
            cands = tuple(int(c) for c in _zipf(
                rng, mix["zipf_items"], 1, n_items, mix["candidates"]) - 1)
        out.append(ServeRequest(
            rid=first_rid + i, user_id=u, prompt=prompt,
            max_new_tokens=int(new[j]), due=float(due[i]),
            interactive=bool(inter[j]), candidates=cands))
    return out


def train_rows(job: dict, seed: int, vocab: int, batch: int
               ) -> Iterator[dict]:
    """Endless packed batches: documents of Zipf item tokens with
    log-normal lengths, each ended by ``eos_id``, concatenated into rows
    of ``seq + 1`` tokens (inputs and next-token targets)."""
    rng = _rng(seed, 0x7A1)
    seq = job["seq"]
    carry = np.zeros(0, np.int64)
    while True:
        need = batch * (seq + 1)
        parts = [carry]
        have = len(carry)
        while have < need:
            n = int(np.clip(rng.lognormal(job["doc_log_mean"],
                                          job["doc_log_sigma"]),
                            2, job["doc_max"]))
            doc = np.minimum(rng.zipf(job["zipf_items"], size=n) + 2,
                             vocab - 1)
            doc[-1] = job["eos_id"]
            parts.append(doc)
            have += n
        flat = np.concatenate(parts)
        carry = flat[need:]
        rows = flat[:need].reshape(batch, seq + 1).astype(np.int32)
        yield {"tokens": rows[:, :-1], "targets": rows[:, 1:],
               "mask": np.ones((batch, seq), np.float32)}

"""Whether what the timed path produced is correct: the comparison with
the plain reference, run once the window has closed.

Serving: a sample of the window's finished requests, drawn from the seed
and always holding the longest, goes through the reference once, prompt
and served tokens together.  Three numbers:

- ``logit_gap_mean``: the mean, over the sampled served tokens, of the gap
  by which a served token's reference logit lies below the reference's
  best at that position (greedy decoding serves the best; rounding may
  serve a near tie);
- ``fused_gap``: the widest difference between the CF head's fused score
  of a candidate and the reference's (prompt-end logit plus the gated CF
  score);
- ``cf_gap``: the widest difference between a CF score and the
  reference's dot product of the two factor rows, over the largest
  reference score.

The control puts the reference in the program's place at the precision
below the configuration's: int8 weights and CF tables (per output channel
and per row) with bfloat16 matmul inputs.  It does not decode: at each
position it reads the gap of the token it ranks first.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchlib import gen
from benchlib.spec import load_reference


def sample_requests(win, seed: int, k: int) -> List[int]:
    """``k`` finished requests of the window, drawn from the seed, the
    longest (prompt and output together) always among them."""
    done = sorted(rid for rid, r in win.records.items()
                  if r.finished is not None and rid in win.outputs)
    if not done:
        return []
    by_rid = {r.rid: r for r in win.reqs}
    longest = max(done, key=lambda rid: len(by_rid[rid].prompt)
                  + len(win.outputs[rid]))
    rest = [r for r in done if r != longest]
    rng = gen._rng(seed, 0xC4EC)
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False))
    return sorted([longest] + [int(x) for x in pick])


def _q8_rows(t: np.ndarray) -> np.ndarray:
    s = np.abs(t).max(axis=1, keepdims=True) / 127.0
    s[s == 0] = 1.0
    return (np.clip(np.round(t / s), -127, 127) * s).astype(np.float32)


def serve_diffs(s, win, k: int, group: int, control: bool = False
                ) -> Dict[str, np.ndarray]:
    """Per served token its gap under the reference; per candidate the
    fused-score difference; per request the CF-score error over its
    largest reference score.  With ``control``, the reference at the
    precision below in the program's place."""
    import jax.numpy as jnp
    ref = load_reference(s.c)
    cz = ref.frozen(s.c)
    rids = sample_requests(win, s.seed, k)
    by_rid = {r.rid: r for r in win.reqs}
    S = s.mix["deployment"]["max_len"]
    K = s.mix["new_tokens_max"]
    alpha = 1.0 / (1.0 + np.exp(-s.gate))
    users, items = s.users, s.items
    if control:
        users, items = _q8_rows(users), _q8_rows(items)
    out = {"gap": [], "fused": [], "cf": []}
    n_real = len(rids)
    while rids and len(rids) % group:
        rids.append(rids[-1])           # whole groups: one compiled shape
    for g in range(0, len(rids), group):
        chunk = rids[g:g + group]
        toks = np.zeros((len(chunk), S), np.int32)
        pos = np.zeros((len(chunk), K), np.int32)
        for i, rid in enumerate(chunk):
            p, o = by_rid[rid].prompt, win.outputs[rid]
            seq = list(p) + o[:-1]
            toks[i, :len(seq)] = seq
            pos[i] = np.minimum(len(p) - 1 + np.arange(K),
                                len(p) - 1 + len(o) - 1)
        lg = np.asarray(ref.logits_at(cz, s.params, jnp.asarray(toks),
                                      jnp.asarray(pos)))
        if control:
            lc = np.asarray(ref.logits_at(cz, s.params, jnp.asarray(toks),
                                          jnp.asarray(pos),
                                          dtype=jnp.bfloat16, quant=True))
        for i, rid in enumerate(chunk[:max(0, n_real - g)]):
            o = win.outputs[rid]
            n = len(o)
            served = (np.argmax(lc[i, :n], axis=-1) if control
                      else np.asarray(o))
            out["gap"].append(lg[i, :n].max(axis=-1)
                              - lg[i, np.arange(n), served])
            r = by_rid[rid]
            if r.candidates is None:
                continue
            cand = np.asarray(r.candidates)
            cf_ref = s.items[cand] @ s.users[r.user_id]
            fused_ref = lg[i, 0, cand] + alpha * cf_ref
            if control:
                cf = items[cand] @ users[r.user_id]
                fused = lc[i, 0, cand] + alpha * cf
            else:
                cf = win.cf[rid]["cf"]
                fused = win.cf[rid]["fused"]
            out["fused"].append(np.abs(fused - fused_ref))
            out["cf"].append(np.atleast_1d(
                np.abs(cf - cf_ref).max() / np.abs(cf_ref).max()))
    return {k: np.concatenate(v) if v else np.asarray([np.inf])
            for k, v in out.items()}


def serve_numbers(s, win, k: int, group: int, control: bool = False
                  ) -> Dict[str, float]:
    """The numbers compared: the mean gap of the served tokens (the widest
    gap swings with the nearest tie among ~50k random logits, and does not
    tell int8 from bfloat16), and the widest fused and CF differences."""
    d = serve_diffs(s, win, k, group, control)
    return {"logit_gap_mean": float(d["gap"].mean()),
            "fused_gap": float(d["fused"].max()),
            "cf_gap": float(d["cf"].max())}

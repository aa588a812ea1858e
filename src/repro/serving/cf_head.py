"""CF head serving: retrieval->rank candidate scoring inside the engine.

The paper's deployment target is an LLM *recommender*: a request is not
just a prompt, it is (user id, candidate item set, interaction history).
This module scores the candidates through the row/col/2D-sharded CF factor
tables — the same ``cf_user`` / ``cf_item`` tables the recsys trainer
shards — and fuses the CF scores with the LM's next-item logits through
:func:`repro.recsys.model.fuse`, the gate both sides of the system share.

The perf core is :class:`repro.embeddings.serving.CachedLookup`: a
frequency-tracked replicated copy of each table's hot head serves cache
hits with zero cross-shard bytes; only the cold tail pays the shard_map
psum / all-to-all.  Scoring is layout- and family-agnostic — the head only
needs the request's last-position LM logits row, which every engine
backend's prefill produces.

    head = CFHead.build(n_users=10_000, n_items=vocab, plan="row",
                        mesh=mesh, cache_rows=256)
    engine = ServingEngine(backend, ecfg, cf_head=head)

Per request the engine calls :meth:`CFHead.score`, which returns the fused
candidate scores and the ranking; cached and uncached configurations are
bit-identical (see the exactness tests), so the cache is purely a comms
optimization.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.embeddings import EmbedSpec, init_table, make_plan
from repro.embeddings.serving import CacheConfig, CachedLookup
from repro.obs.trace import NULL_TRACER, Tracer


@dataclasses.dataclass(frozen=True)
class CFConfig:
    """Placement + cache knobs of the serving CF head."""

    plan: str = "replicated"        # replicated | row | col | row_col
    cache_rows: int = 0             # hot-row replica capacity (0 = off)
    decay: float = 0.98
    elect_every: int = 1
    miss_quantum: int = 8
    row_axis: str = "model"
    col_axis: str = "data"


class CFHead:
    """Sharded CF scoring head for the serving engine.

    Owns the ``cf_user`` / ``cf_item`` tables (each behind a
    :class:`CachedLookup`) and the fusion gate.  ``score`` is one
    retrieval->rank step: look up the user's factor row and the candidate
    item rows, dot them into CF scores, fuse with the LM's last-position
    logits at the candidate ids, rank.
    """

    def __init__(self, user_table, item_table, fusion_gate=0.0,
                 cfg: CFConfig = CFConfig(), mesh: Optional[Mesh] = None):
        u = np.asarray(user_table, np.float32)
        it = np.asarray(item_table, np.float32)
        if u.shape[1] != it.shape[1]:
            raise ValueError(f"cf_dim mismatch: user {u.shape} vs "
                             f"item {it.shape}")
        self.cfg = cfg
        self.fusion_gate = jnp.asarray(fusion_gate, jnp.float32)
        plan = make_plan(cfg.plan, row_axis=cfg.row_axis,
                         col_axis=cfg.col_axis)
        cache = CacheConfig(rows=cfg.cache_rows, decay=cfg.decay,
                            elect_every=cfg.elect_every,
                            miss_quantum=cfg.miss_quantum)
        self.lookups: Dict[str, CachedLookup] = {
            "cf_user": CachedLookup(
                EmbedSpec("cf_user", rows=u.shape[0], dim=u.shape[1]),
                plan, u, mesh=mesh, cache=cache),
            "cf_item": CachedLookup(
                EmbedSpec("cf_item", rows=it.shape[0], dim=it.shape[1]),
                plan, it, mesh=mesh, cache=cache),
        }
        self.requests_scored = 0
        self._reads = 0         # its own device reads (the lookups count theirs)
        self.set_tracer(NULL_TRACER)

    def set_tracer(self, tracer: Tracer, track: str = "engine") -> None:
        """Time each scoring phase on ``tracer`` (its serving engine's),
        nested on ``track``; the lookups get it too."""
        self.tracer, self.track = tracer, track
        for lk in self.lookups.values():
            lk.tracer, lk.track = tracer, track

    @property
    def host_syncs(self) -> int:
        """Reads of device arrays to the host, each a blocking sync."""
        return self._reads + sum(lk.host_syncs
                                 for lk in self.lookups.values())

    @classmethod
    def build(cls, n_users: int, n_items: int, cf_dim: int = 16, *,
              seed: int = 0, plan: str = "replicated", cache_rows: int = 0,
              mesh: Optional[Mesh] = None, fusion_gate: float = 0.0,
              **knobs) -> "CFHead":
        """Fresh factor tables (the :func:`repro.embeddings.init_table`
        convention) under one plan; ``knobs`` feed :class:`CFConfig`."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        u = init_table(k1, EmbedSpec("cf_user", rows=n_users, dim=cf_dim))
        it = init_table(k2, EmbedSpec("cf_item", rows=n_items, dim=cf_dim))
        cfg = CFConfig(plan=plan, cache_rows=cache_rows, **knobs)
        return cls(u, it, fusion_gate=fusion_gate, cfg=cfg, mesh=mesh)

    # -- scoring --------------------------------------------------------------

    def score(self, user_id: int, candidates: Sequence[int],
              lm_logits_row=None) -> Dict:
        """One retrieval->rank step.

        ``lm_logits_row`` is the request's last-position (V,) LM logits
        from prefill; ``None`` ranks on CF scores alone (pure retrieval).
        Returns numpy arrays so the engine can store/compare them without
        device transfers: ``cf`` (C,), ``fused`` (C,), ``ranking`` (the
        candidate ids, best first), plus cache hit/miss counts for this
        call.
        """
        from repro.recsys import model as rec_model
        tr, trk = self.tracer, self.track
        cand = np.asarray(candidates, np.int64).reshape(-1)
        with tr.span("cf.user", track=trk):
            u_rows, u_stats = self.lookups["cf_user"](np.asarray([user_id]))
        with tr.span("cf.items", track=trk):
            i_rows, i_stats = self.lookups["cf_item"](cand)
        with tr.span("cf.logits_row", track=trk):
            if lm_logits_row is None:
                lm = np.zeros(len(cand), np.float32)
            else:
                if isinstance(lm_logits_row, jax.Array):
                    self._reads += 1
                lm = np.asarray(lm_logits_row, np.float32)[cand]
        with tr.span("cf.fuse_rank", track=trk):
            cf = i_rows @ u_rows[0]                      # (C,) f32
            fused = np.asarray(rec_model.fuse(
                jnp.asarray(lm), jnp.asarray(cf), self.fusion_gate))
            self._reads += 1
            order = np.argsort(-fused, kind="stable")
        self.requests_scored += 1
        return {
            "cf": cf, "fused": fused,
            "ranking": cand[order],
            "hits": u_stats["hits"] + i_stats["hits"],
            "misses": u_stats["misses"] + i_stats["misses"],
        }

    # -- table updates --------------------------------------------------------

    def update_rows(self, table: str, ids, rows,
                    refresh: bool = True) -> np.ndarray:
        """Land a trainer update on one table (rows-touched refresh of the
        hot-row replica unless ``refresh=False``)."""
        return self.lookups[table].update_rows(ids, rows, refresh=refresh)

    def refresh_touched(self, table: str, touched) -> None:
        self.lookups[table].refresh_touched(touched)

    # -- accounting -----------------------------------------------------------

    @property
    def hits(self) -> int:
        return sum(lk.hits for lk in self.lookups.values())

    @property
    def misses(self) -> int:
        return sum(lk.misses for lk in self.lookups.values())

    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0

    @property
    def cache_rows_live(self) -> int:
        return sum(lk.n_cached for lk in self.lookups.values())

    def summary(self) -> Dict:
        return {
            "plan": self.cfg.plan,
            "cache_rows": self.cfg.cache_rows,
            "cache_rows_live": self.cache_rows_live,
            "requests_scored": self.requests_scored,
            "hits": self.hits, "misses": self.misses,
            "hit_rate": self.hit_rate,
            "tables": {n: lk.summary() for n, lk in self.lookups.items()},
        }

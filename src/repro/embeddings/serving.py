"""Serving-side embedding lookups: a frequency-cached hot-row replica in
front of the sharded shard_map exchange.

Zipfian id traffic concentrates lookups on a small head of rows ("Training
Recommender Systems at Scale"): a request batch of C candidate ids mostly
revisits the same few hundred hot items.  Under the row/col/2D sharding
plans every one of those lookups pays a cross-shard exchange — a psum of
(U, D) partials and/or an all-to-all of column slices — even though the
answer was the same bytes as last request.  This module converts that
exchange from O(C·D) to O(C_tail·D):

* :class:`FreqTracker` — exact decayed-count popularity over row ids (the
  sketch-free baseline; counts halve every ``1/(1-decay)`` observations so
  yesterday's hot head ages out).
* :class:`HotRowCache` — a replicated host-side copy of the top-K rows by
  decayed count, with an id -> slot map.  Rows are **exact copies** of the
  authoritative table rows, re-gathered at election and after table
  updates, so a cache hit is bit-identical to the sharded path.
* :class:`CachedLookup` — the serving lookup over one table: partition the
  requested ids into hits (gathered from the replica — no collective) and
  misses (bucket-padded through the existing ``make_sharded_lookup``
  shard_map exchange), stitched back in request order.  Rows-touched
  refresh (:func:`repro.embeddings.update.rows_touched`) keeps the replica
  exact after trainer updates.

Exactness argument: the sharded lookup is bit-identical to a replicated
gather (the psum adds exact-zero partials from non-owner shards, the
all-to-all is pure data movement), and cache rows are byte copies of the
same table — so the cached path equals the uncached path bit-for-bit at
every plan, which the tests and the 8-device check assert.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.embeddings.lookup import make_sharded_lookup
from repro.embeddings.table import (EmbedPlan, EmbedSpec, make_plan,
                                    named_sharding)
from repro.embeddings.update import rows_touched
from repro.obs.trace import NULL_TRACER


class FreqTracker:
    """Exact decayed-count row popularity (host side, numpy).

    ``observe`` decays every count by ``decay`` then adds 1 per requested
    id; ``top_k`` returns the hottest row ids (sorted, count > 0 only) —
    the election set for :class:`HotRowCache`.  Exact counting keeps the
    cache contents deterministic for a given request stream; a CM-sketch
    drop-in would trade that for O(1) memory at web-scale vocabularies.
    """

    def __init__(self, n_rows: int, decay: float = 0.98):
        if not 0.0 < decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay}")
        self.n_rows = n_rows
        self.decay = decay
        self.counts = np.zeros(n_rows, np.float64)

    def observe(self, ids: np.ndarray) -> None:
        flat = np.asarray(ids, np.int64).reshape(-1)
        self.counts *= self.decay
        np.add.at(self.counts, flat, 1.0)

    def top_k(self, k: int) -> np.ndarray:
        k = min(int(k), self.n_rows)
        if k <= 0:
            return np.empty(0, np.int64)
        idx = np.argpartition(-self.counts, k - 1)[:k]
        idx = idx[self.counts[idx] > 0.0]
        return np.sort(idx.astype(np.int64))


class HotRowCache:
    """Replicated copy of the top-K hottest rows of one table.

    ``rows[slot_of[id]]`` is a byte copy of ``table[id]``; hits skip the
    cross-shard exchange entirely.  ``refresh`` re-elects the head from
    the tracker; ``refresh_touched`` re-gathers only the cached rows a
    table update touched (the trainer's rows-touched set), restoring
    bit-exactness without a full re-election.
    """

    def __init__(self, n_rows: int, capacity: int, decay: float = 0.98):
        self.capacity = int(capacity)
        self.tracker = FreqTracker(n_rows, decay)
        self.ids = np.empty(0, np.int64)
        self.slot_of: Dict[int, int] = {}
        self.rows = np.empty((0, 0), np.float32)
        self.hits = 0
        self.misses = 0

    @property
    def n_cached(self) -> int:
        return len(self.ids)

    def refresh(self, host_table: np.ndarray) -> None:
        """Re-elect the top-K head; gather rows only for newly elected
        ids.  Rows already cached keep their bytes — the replica is not
        re-read from the table on election, which is what makes the
        rows-touched refresh after updates load-bearing (and what a real
        deployment does: election moves the membership set, not the
        data)."""
        new_ids = self.tracker.top_k(self.capacity)
        rows = np.empty((len(new_ids), host_table.shape[1]), np.float32)
        held = np.fromiter((self.slot_of.get(int(i), -1) for i in new_ids),
                           np.int64, count=len(new_ids))
        keep = held >= 0
        if keep.any():
            rows[keep] = self.rows[held[keep]]
        if (~keep).any():
            rows[~keep] = host_table[new_ids[~keep]]
        self.ids = new_ids
        self.slot_of = {int(i): s for s, i in enumerate(new_ids)}
        self.rows = rows

    def refresh_touched(self, touched: np.ndarray,
                        host_table: np.ndarray) -> None:
        """Re-gather cached rows intersecting ``touched`` (unique row ids
        from the update batch); untouched cache slots keep their bytes."""
        if not len(self.ids):
            return
        stale = np.isin(self.ids, np.asarray(touched, np.int64))
        if stale.any():
            self.rows[stale] = host_table[self.ids[stale]]

    def plan_lookup(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(hit mask, cache slot per id; -1 on miss) + hit/miss counters."""
        flat = np.asarray(ids, np.int64).reshape(-1)
        slots = np.fromiter((self.slot_of.get(int(i), -1) for i in flat),
                            np.int64, count=len(flat))
        hit = slots >= 0
        self.hits += int(hit.sum())
        self.misses += int((~hit).sum())
        return hit, slots


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Knobs of the hot-row replica on one serving lookup."""

    rows: int = 0                  # cache capacity (0 = cache off)
    decay: float = 0.98            # per-observation count decay
    elect_every: int = 1           # lookups between head re-elections
    #   (election is host-side top-K + a <= capacity-row gather — cheap
    #   next to the exchange it saves; raise it to model a server that
    #   re-elects on a timer instead of per request)
    miss_quantum: int = 8          # miss-path pad bucket (x dp size)


class CachedLookup:
    """One table's serving lookup: hot-row replica first, shard_map
    exchange only for the cold tail.

    ``table`` is the authoritative (rows, dim) array, placed under
    ``plan`` on ``mesh`` (trivial 1-device meshes work; ``mesh=None``
    keeps the table replicated and skips shard_map entirely).  Calls are
    host-side: ``lookup(ids) -> (n, D) float32`` exactly equal to
    ``table[ids]``, plus per-call hit/miss stats.  The miss path pads to
    a bucket (a multiple of the DP-axis size times ``miss_quantum``) so
    the jitted shard_map sees a handful of static shapes.
    """

    def __init__(self, spec: EmbedSpec, plan: EmbedPlan,
                 table, mesh: Optional[Mesh] = None,
                 cache: CacheConfig = CacheConfig(),
                 dp_axis: str = "data"):
        self.spec, self.plan, self.ccfg = spec, plan, cache
        self.dp_axis = dp_axis
        # always copy: the caller's array may be a read-only jax buffer
        # view, and update_rows writes in place
        self._host = np.array(table, dtype=np.float32, order="C")
        if self._host.shape != (spec.rows, spec.dim):
            raise ValueError(f"{spec.name}: table shape {self._host.shape} "
                             f"!= spec ({spec.rows}, {spec.dim})")
        self.mesh = mesh
        self._ndp = 1
        self._sharded = None
        if mesh is not None and plan.kind != "replicated":
            self._sharded = make_sharded_lookup(mesh, spec, plan, dp_axis)
            self._ndp = dict(mesh.shape)[dp_axis]
            self._table_dev = jax.device_put(
                jnp.asarray(self._host), named_sharding(mesh, plan))
            self._ids_sharding = NamedSharding(mesh, P(dp_axis))
        else:
            self._table_dev = jnp.asarray(self._host)
        self.cache = (HotRowCache(spec.rows, cache.rows, cache.decay)
                      if cache.rows > 0 else None)
        self.calls = 0
        self.exchanged_ids = 0          # ids that took the sharded path
        self.host_syncs = 0             # device reads to the host
        self.tracer, self.track = NULL_TRACER, "main"

    # -- cache bookkeeping ---------------------------------------------------

    @property
    def hits(self) -> int:
        return self.cache.hits if self.cache else 0

    @property
    def misses(self) -> int:
        return self.cache.misses if self.cache else 0

    @property
    def n_cached(self) -> int:
        return self.cache.n_cached if self.cache else 0

    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0

    # -- the lookup ----------------------------------------------------------

    def _miss_bucket(self, n: int) -> int:
        """Static miss-path shapes: the next power-of-two multiple of
        (quantum x DP size) — col plans shard the id vector over the DP
        axis, so the padded count must divide by it."""
        q = max(1, self.ccfg.miss_quantum) * self._ndp
        b = q
        while b < n:
            b *= 2
        return b

    def _exchange(self, ids: np.ndarray) -> np.ndarray:
        """table[ids] through the sharded (or replicated) path."""
        n = len(ids)
        self.host_syncs += 1
        if self._sharded is None:
            out = np.asarray(self._table_dev[jnp.asarray(ids, jnp.int32)])
            self.exchanged_ids += n
            return out
        pad = self._miss_bucket(n)
        padded = np.zeros(pad, np.int32)
        padded[:n] = ids
        ids_dev = jax.device_put(jnp.asarray(padded), self._ids_sharding)
        out = np.asarray(self._sharded(self._table_dev, ids_dev))[:n]
        self.exchanged_ids += pad
        return out

    def __call__(self, ids) -> Tuple[np.ndarray, Dict[str, int]]:
        """(rows (n, D) float32 == table[ids] bit-for-bit, stats)."""
        flat = np.asarray(ids, np.int64).reshape(-1)
        self.calls += 1
        tr, trk = self.tracer, self.track
        if self.cache is None:
            with tr.span("cf.gather", track=trk):
                rows = self._exchange(flat)
            return rows, {"hits": 0, "misses": len(flat)}
        with tr.span("cf.cache.plan", track=trk):
            self.cache.tracker.observe(flat)
            hit, slots = self.cache.plan_lookup(flat)
            rows = np.empty((len(flat), self.spec.dim), np.float32)
            if hit.any():
                rows[hit] = self.cache.rows[slots[hit]]
            # the hits are copied out, so the head can be re-elected now:
            # the misses come from the table, not the replica
            if self.ccfg.elect_every and \
                    self.calls % self.ccfg.elect_every == 0:
                self.cache.refresh(self._host)
        n_miss = int((~hit).sum())
        if n_miss:
            with tr.span("cf.gather", track=trk):
                rows[~hit] = self._exchange(flat[~hit])
        return rows, {"hits": int(hit.sum()), "misses": n_miss}

    # -- table updates / staleness -------------------------------------------

    def _sync_device(self) -> None:
        if self._sharded is not None:
            self._table_dev = jax.device_put(
                jnp.asarray(self._host), named_sharding(self.mesh, self.plan))
        else:
            self._table_dev = jnp.asarray(self._host)

    def update_rows(self, ids, rows, refresh: bool = True) -> np.ndarray:
        """Land a trainer update: ``table[ids] = rows`` (duplicate ids:
        last write wins, matching a sequential scatter).  With ``refresh``
        the cached copies of the touched rows are re-gathered immediately
        (the rows-touched hook); ``refresh=False`` leaves the replica
        stale until :meth:`refresh_touched` — what the staleness tests
        exercise.  Returns the unique touched-row ids."""
        flat = np.asarray(ids, np.int64).reshape(-1)
        self._host[flat] = np.asarray(rows, np.float32)
        self._sync_device()
        touched = np.asarray(
            rows_touched(jnp.asarray(flat), self.spec.rows))
        touched = touched[touched < self.spec.rows]
        if refresh:
            self.refresh_touched(touched)
        return touched

    def refresh_touched(self, touched) -> None:
        """Rows-touched cache refresh: restore bit-exactness for the
        cached rows a table update invalidated."""
        if self.cache is not None:
            self.cache.refresh_touched(np.asarray(touched, np.int64),
                                       self._host)

    def summary(self) -> Dict:
        return {
            "table": self.spec.name, "plan": self.plan.kind,
            "cache_rows": self.ccfg.rows, "cached_now": self.n_cached,
            "hits": self.hits, "misses": self.misses,
            "hit_rate": self.hit_rate,
            "lookups": self.calls, "exchanged_ids": self.exchanged_ids,
        }


def make_cached_lookup(name: str, table, kind: str = "replicated",
                       mesh: Optional[Mesh] = None,
                       cache: CacheConfig = CacheConfig(),
                       row_axis: str = "model", col_axis: str = "data",
                       ) -> CachedLookup:
    """Convenience: spec from the table's shape, plan from ``kind``."""
    t = np.asarray(table)
    spec = EmbedSpec(name, rows=t.shape[0], dim=t.shape[1])
    plan = make_plan(kind, row_axis=row_axis, col_axis=col_axis)
    return CachedLookup(spec, plan, t, mesh=mesh, cache=cache)

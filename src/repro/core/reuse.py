"""Lineage-based reuse cache (SystemDS §4.1, "Reuse of Intermediates").

Intermediates are identified by their lineage hash (hash of the lineage
DAG). Before executing an instruction, the runtime probes the cache for
*full reuse*; *partial reuse* is realized by the compensation-plan
rewrites in `repro.core.rewrites.distribute_for_reuse`, which decompose
operators (gram/xtv over rbind/cbind) so their pieces become cache hits.

Eviction follows SystemDS's cost-and-size heuristic: keep entries with
high (compute-cost / byte), weighted by recency (LRU decay).

The same lineage contract covers the device copy of a leaf: a leaf is
immutable from `input_tensor` on, so its copy on the device is reusable
under the leaf's lineage id for as long as its intermediates are
(`leaf_upload`). Copies live beside the entries, under the same byte
budget, with their own counters; they take only budget no entry holds
and are evicted before any entry, so every entry's admission, eviction
and hit is what it would be without them.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import numpy as np

# Below this compute cost (seconds) an intermediate is not worth caching.
# The runtime applies this gate at *compile time* via the cost model
# (`repro.core.costmodel.PROBE_MIN_COST_S`) and calls `put(gated=False)`;
# the measured-cost check below only applies to external callers using
# the cache standalone.
MIN_CACHE_COST_S = 20e-6
# Standalone-caller admission only: below this size a measured-cheap
# value is kept anyway (scalars/metadata cost nothing to hold). The
# runtime's compile-time probe gate does not consult this — sub-threshold
# intermediates are fused through, not cached.
ALWAYS_CACHE_BYTES = 1 << 12


def nbytes(value) -> int:
    """True byte size of a cached value.

    Sparse (BCOO) entries are accounted at their sparse size —
    data + indices buffers — checked *before* the generic `.nbytes`
    attribute so wrappers exposing a dense-shaped `nbytes` don't
    overcharge, and so entries lacking `.nbytes` entirely don't fall
    through to a stub size that would break eviction pressure.
    """
    if isinstance(value, (tuple, list)):
        # chunk-level partial-aggregate entries (see the streaming
        # executor) cache one tuple per row bucket
        return sum(nbytes(v) for v in value)
    sites = getattr(value, "sites", None)  # FederatedTensor intermediates
    if sites is not None:
        return sum(nbytes(getattr(s, "data", s)) for s in sites)
    data = getattr(value, "data", None)  # BCOO and friends
    indices = getattr(value, "indices", None)
    if data is not None and indices is not None:
        total = 0
        for buf in (data, indices):
            nb = getattr(buf, "nbytes", None)
            if nb is None:
                nb = int(np.size(buf)) * np.dtype(buf.dtype).itemsize
            total += int(nb)
        return total
    if hasattr(value, "nbytes"):
        return int(value.nbytes)
    size, dtype = getattr(value, "size", None), getattr(value, "dtype", None)
    if size is not None and dtype is not None:
        return int(size) * np.dtype(dtype).itemsize
    return 64


@dataclass
class CacheEntry:
    value: Any
    size: int
    cost: float          # seconds it took to compute
    last_used: float
    hits: int = 0


@dataclass
class ReuseStats:
    probes: int = 0
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    bytes_cached: int = 0
    time_saved: float = 0.0   # Σ cost of hit entries
    # device copies of leaves (`ReuseCache.leaf_upload`), apart from
    # the entries' counts above
    uploads: int = 0           # misses that copied a leaf to the device
    upload_hits: int = 0       # calls served by a resident copy
    upload_bytes: int = 0      # resident bytes of the copies
    upload_evictions: int = 0

    def as_dict(self) -> dict:
        return dict(probes=self.probes, hits=self.hits, misses=self.misses,
                    evictions=self.evictions, bytes=self.bytes_cached,
                    time_saved_s=round(self.time_saved, 6),
                    uploads=self.uploads, upload_hits=self.upload_hits,
                    upload_bytes=self.upload_bytes,
                    upload_evictions=self.upload_evictions)


class ReuseCache:
    """Lineage-hash keyed intermediate cache with cost/size eviction."""

    def __init__(self, budget_bytes: int = 4 << 30,
                 policy: str = "costsize"):
        assert policy in ("costsize", "lru")
        self.budget = int(budget_bytes)
        self.policy = policy
        self.entries: dict[str, CacheEntry] = {}
        self.uploads: dict[tuple, CacheEntry] = {}  # leaf device copies
        self.stats = ReuseStats()

    # -- interface ----------------------------------------------------------
    def probe(self, lhash: str) -> Optional[Any]:
        self.stats.probes += 1
        e = self.entries.get(lhash)
        if e is None:
            self.stats.misses += 1
            return None
        e.hits += 1
        e.last_used = time.perf_counter()
        self.stats.hits += 1
        self.stats.time_saved += e.cost
        return e.value

    def put(self, lhash: str, value: Any, cost: float,
            gated: bool = True) -> None:
        """Insert an entry. `gated=False` skips the measured-cost
        worth-keeping check — used by the runtime, whose compile-time
        cost model already admitted the value as a probe point (keeps
        admission identical across interpreter and fused modes)."""
        size = nbytes(value)
        if gated and cost < MIN_CACHE_COST_S and size > ALWAYS_CACHE_BYTES:
            return  # not worth the pool space
        if size > self.budget:
            return
        if lhash in self.entries:
            return
        self._make_room(size)
        self.entries[lhash] = CacheEntry(value=value, size=size, cost=cost,
                                         last_used=time.perf_counter())
        self.stats.bytes_cached += size

    def leaf_upload(self, key: tuple, host: np.ndarray,
                    copy: Callable[[np.ndarray], Any], cost: float) -> Any:
        """The device copy of the leaf `key` names (its lineage id, dtype
        and shape), made by `copy(host)` on a miss; `cost` is the copy's
        estimated seconds, its eviction value. A copy is admitted only
        into budget no entry holds, displacing older copies but never
        an entry; a leaf that does not fit is returned as `host`, for
        the executable to copy per call as it would without a cache."""
        e = self.uploads.get(key)
        if e is not None:
            e.hits += 1
            e.last_used = time.perf_counter()
            self.stats.upload_hits += 1
            return e.value
        size = nbytes(host)
        if self.stats.bytes_cached + size > self.budget:
            return host
        self._evict(self.uploads, size)
        value = copy(host)
        self.uploads[key] = CacheEntry(value=value, size=size, cost=cost,
                                       last_used=time.perf_counter())
        self.stats.uploads += 1
        self.stats.upload_bytes += size
        return value

    def clear(self) -> None:
        self.entries.clear()
        self.uploads.clear()
        self.stats.bytes_cached = 0
        self.stats.upload_bytes = 0

    # -- eviction -------------------------------------------------------------
    def _score(self, e: CacheEntry, now: float) -> float:
        if self.policy == "lru":
            return -(now - e.last_used)
        # costsize: value density (seconds saved per byte), light recency decay
        age = now - e.last_used
        return (e.cost / max(e.size, 1)) / (1.0 + 0.01 * age)

    def _make_room(self, need: int) -> None:
        # leaf copies go first: an entry is evicted only once no copy
        # holds budget, exactly when it would be without copies
        self._evict(self.uploads, need)
        self._evict(self.entries, need)

    def _evict(self, pool: dict, need: int) -> None:
        st = self.stats
        if st.bytes_cached + st.upload_bytes + need <= self.budget:
            return
        now = time.perf_counter()
        victims = sorted(pool.items(), key=lambda kv: self._score(kv[1], now))
        for key, e in victims:
            if st.bytes_cached + st.upload_bytes + need <= self.budget:
                break
            del pool[key]
            if pool is self.uploads:
                st.upload_bytes -= e.size
                st.upload_evictions += 1
            else:
                st.bytes_cached -= e.size
                st.evictions += 1

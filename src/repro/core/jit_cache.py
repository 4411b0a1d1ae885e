"""Process-wide cache of compiled segment executables.

Keyed by (segment canonical structural key, concrete input signature
(shapes + dtypes)), so structurally identical segments compiled from
*different* plans — HPO loops, CV folds, repeated `PreparedScript`
construction — share one XLA executable and replay without re-tracing.

On a miss the segment closure is lowered ahead-of-time
(`jax.jit(fn).lower(*args).compile()`), so replay calls skip
dispatch-time signature checks; the runtime books that compile as its
`ds.jit.compile` span (`repro.core.tracing`).

The cache is bounded: LRU eviction on BOTH an entry cap and a resident
code-byte cap (executable size from XLA's `memory_analysis` when
available, a flat estimate otherwise) — long sessions sweeping many
plan shapes (growing `parfor` grids) stay at a bounded footprint, and
eviction/hit/miss counters surface in
`RuntimeStats.as_dict()['jit_cache']`.
"""
from __future__ import annotations

import warnings
from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
from jax.experimental.sparse import BCOO as _BCOO

from . import faults

# Caps of the process-wide cache.
DEFAULT_CAPACITY = 512
DEFAULT_BYTE_CAPACITY = 256 << 20
# Executables that expose no memory analysis are charged a flat size so
# the byte cap still exerts pressure instead of silently unbounding.
FALLBACK_EXE_BYTES = 64 << 10


@dataclass
class JitCacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0        # entries dropped by the entry/byte caps
    bytes_cached: int = 0     # resident generated-code bytes (estimate)
    pinned: int = 0           # entries exempt from LRU (deploy-warmed)

    def as_dict(self) -> dict:
        return dict(hits=self.hits, misses=self.misses,
                    evictions=self.evictions,
                    bytes_cached=self.bytes_cached,
                    pinned=self.pinned)


def arg_signature(args) -> tuple:
    """Shape/dtype(/weak-type) signature of concrete call arguments.

    weak_type matters: AOT-compiled executables reject aval mismatches,
    and a weak-typed jax scalar (e.g. a literal crossing a segment
    boundary) has a different aval than a strong-typed array of the same
    shape/dtype. BCOO arguments additionally carry their nse (buffer
    size) — two sparse matrices of equal shape but different nnz have
    different avals and need separate executables.
    """
    out = []
    for a in args:
        if isinstance(a, _BCOO):
            # pytree flags are part of the aval too: an executable
            # compiled for unique_indices=True rejects a False-flagged
            # BCOO of identical shape/dtype/nse
            out.append(("bcoo", tuple(a.shape), str(a.dtype), int(a.nse),
                        bool(a.unique_indices), bool(a.indices_sorted)))
        else:
            out.append(
                (tuple(getattr(a, "shape", ())),
                 str(getattr(a, "dtype", type(a).__name__)),
                 bool(getattr(a, "weak_type", False))))
    return tuple(out)


def mesh_key_tag(mesh_tag: str, in_tags, out_tags) -> str:
    """Segment-key suffix for shard_map-lowered executables.

    A sharded segment closes over a concrete device mesh and per-arg
    partition specs — none of which appear in the argument signature
    (global shapes are identical). Suffixing the mesh shape and the
    's'/'r' spec tags keeps sharded executables from ever colliding
    with the local executable of the same segment body, or with the
    same body sharded over a different mesh shape.
    """
    return (f"|mesh:{mesh_tag}|in:{''.join(in_tags)}"
            f"|out:{''.join(out_tags)}")


def _exe_nbytes(exe: Any) -> int:
    """Resident-size estimate of one compiled executable (generated
    code; argument buffers are owned by the caller, not the cache)."""
    try:
        ma = exe.memory_analysis()
        nb = int(getattr(ma, "generated_code_size_in_bytes", 0))
        if nb > 0:
            return nb
    except Exception:
        pass
    return FALLBACK_EXE_BYTES


class JitProgramCache:
    """LRU cache: (segment key, input signature) -> compiled executable.

    Bounded by `capacity` entries AND `byte_capacity` resident code
    bytes; the least-recently-used entries are evicted when either cap
    is exceeded (`stats.evictions` / `stats.bytes_cached`)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 byte_capacity: int = DEFAULT_BYTE_CAPACITY):
        self.capacity = int(capacity)
        self.byte_capacity = int(byte_capacity)
        # key -> (executable, code bytes)
        self._entries: "OrderedDict[tuple, tuple[Callable, int]]" = \
            OrderedDict()
        # keys exempt from LRU eviction (deploy-warmed serving
        # executables: evicting one would put trace+compile back on a
        # request's critical path — exactly what deploy-time warmup paid
        # to remove)
        self._pinned: set[tuple] = set()
        # active pinning() recorders (normally 0 or 1)
        self._recorders: list[set[tuple]] = []
        self.stats = JitCacheStats()

    def __len__(self) -> int:
        return len(self._entries)

    def lookup(self, seg_key: str, args) -> tuple[tuple, Optional[Callable]]:
        """Return (full key, executable-or-None); counts hit/miss."""
        key = (seg_key, arg_signature(args))
        for rec in self._recorders:
            rec.add(key)
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            self.stats.hits += 1
            return key, entry[0]
        self.stats.misses += 1
        return key, None

    def compile(self, key: tuple, fn: Callable, args,
                donate_argnums: tuple = ()) -> Callable:
        """Compile `fn` for `args`, store under `key`; returns the
        executable.

        `donate_argnums` marks dead-after-segment arguments whose
        buffers XLA may alias into the outputs (the async pipeline's
        `_free`-uid candidates). Donation is baked into the caller's
        `key` (a `|don:` seg-key suffix), so a donated executable can
        never be replayed with live arguments under the plain key."""
        # seeded fault injection (ISSUE 10): a `compile` rule fails this
        # call before any tracing happens — callers degrade to the
        # interpreter (segments) or retry (site sub-segments)
        faults.compile_entry(key[0] if isinstance(key, tuple) else key)
        jitted = jax.jit(fn, donate_argnums=donate_argnums) \
            if donate_argnums else jax.jit(fn)
        if donate_argnums:
            # XLA warns when a donated buffer finds no same-shape/dtype
            # output to alias — harmless (the buffer is dead either way)
            # and would spam every compile
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
                exe: Any = jitted.lower(*args).compile()
        else:
            exe = jitted.lower(*args).compile()
        nb = _exe_nbytes(exe)
        old = self._entries.pop(key, None)
        if old is not None:  # racing recompile of the same key
            self.stats.bytes_cached -= old[1]
        self._entries[key] = (exe, nb)
        self.stats.bytes_cached += nb
        self._evict()
        return exe

    def _evict(self) -> None:
        # Walk LRU-first, skipping pinned entries — pinned executables
        # still occupy entry/byte budget (their pressure falls on the
        # unpinned population) but can never be dropped. Keys recorded
        # by an open pinning() block are protected already: deploy-time
        # warmup compiles MORE executables than `capacity` allows in
        # sequence, and evicting bucket 2 while warming bucket 16 would
        # defeat the warmup. The newest unpinned entry is never evicted
        # either: a single over-budget executable is still the one we
        # must run.
        protected = self._pinned.union(*self._recorders) \
            if self._recorders else self._pinned
        while True:
            unpinned = [k for k in self._entries if k not in protected]
            if len(unpinned) <= 1:
                break
            over = (len(self._entries) > self.capacity
                    or self.stats.bytes_cached > self.byte_capacity)
            if not over:
                break
            key = unpinned[0]
            _, nb = self._entries.pop(key)
            self.stats.bytes_cached -= nb
            self.stats.evictions += 1

    # -- pinning (serving deploy-time warmup) --------------------------
    def pin(self, key: tuple) -> None:
        """Exempt `key` from LRU eviction (no-op if already pinned)."""
        if key not in self._pinned:
            self._pinned.add(key)
            self.stats.pinned = len(self._pinned)

    def unpin(self, key: tuple) -> None:
        self._pinned.discard(key)
        self.stats.pinned = len(self._pinned)
        self._evict()  # unpinned entries are back under the caps

    def unpin_all(self, keys=None) -> None:
        """Unpin `keys` (or everything) and re-apply the caps."""
        if keys is None:
            self._pinned.clear()
        else:
            self._pinned.difference_update(keys)
        self.stats.pinned = len(self._pinned)
        self._evict()

    @contextmanager
    def pinning(self):
        """Record every cache key touched inside the block and pin the
        ones resident at exit. `ModelServer.deploy` wraps its bucket
        warmup in this so the LRU can never evict a serving executable
        mid-flight; the yielded set is kept so `shutdown` can unpin."""
        rec: set[tuple] = set()
        self._recorders.append(rec)
        try:
            yield rec
        finally:
            # by identity: a nested block's set may equal this one
            self._recorders = [r for r in self._recorders if r is not rec]
            for key in rec:
                if key in self._entries:
                    self.pin(key)

    def clear(self) -> None:
        self._entries.clear()
        self._pinned.clear()
        self.stats.bytes_cached = 0
        self.stats.pinned = 0


_global_cache: Optional[JitProgramCache] = None


def get_jit_cache() -> JitProgramCache:
    global _global_cache
    if _global_cache is None:
        _global_cache = JitProgramCache()
    return _global_cache


def clear_jit_cache() -> None:
    """Drop all compiled executables (tests / memory pressure)."""
    if _global_cache is not None:
        _global_cache.clear()

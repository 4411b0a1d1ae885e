"""Spans of the runtime: one table of host seconds, on the profiler's clock.

``with rt.stats.spans("ds.<name>"):`` enters a
`jax.profiler.TraceAnnotation` of that name, which records only while a
profiler runs and then shares the device trace's clock, and adds the
block's `perf_counter` seconds and a count to the in-memory table
``{name: [n, seconds]}`` that `RuntimeStats.as_dict()["spans"]` exports.

Each name is the inclusive time of one layer boundary; on the main thread
sibling spans do not overlap, so their seconds add up to the host time
they cover. The names are fixed (no per-segment strings):

==========================  ==============================================
``ds.parfor.build``         `parfor` declaring the k configurations' DAGs
``ds.parfor.plan``          `compile_batched` and `choose_mode`
``ds.parfor.config``        one configuration of the sequential loop
``ds.compile_plan``         `LineageRuntime.evaluate` -> `compile_plan`
``ds.bind``                 `_bind_leaves`
``ds.probe``                lineage hash and reuse probe (segment, chunk)
``ds.jit.compile``          jit-cache miss: trace, lower, compile
``ds.dispatch``             calling a non-streamed executable or host op
``ds.upload``               copying a registry leaf to the device once,
                            for the reuse cache, until the copy is ready
``ds.shard.place``          placing a sharded segment's host operand on
                            the mesh, once a run, until the copy is ready
``ds.stream.fingerprint``   bucket and replicated-operand fingerprints
``ds.stream.dispatch``      calling the bucket executable, with the
                            host-to-device copy of a numpy slice
``ds.stream.combine``       accumulating and concatenating partials
``ds.stream.prep``          slicing a bucket on the prefetch worker
                            (overlapped, like ``ds.stream.copy``)
``ds.stream.copy``          the prefetch worker copying a dense bucket
                            to the device, until the copy is ready
``ds.wait``                 every host block on device results
``ds.serve.batch``          one `ModelServer` batch
==========================  ==============================================
"""
from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Iterable

from jax.profiler import TraceAnnotation

# longest XLA module name a segment closure gets (`jit_` is prefixed)
FN_NAME_MAX = 60


class _Span:
    __slots__ = ("log", "name", "ann", "t0")

    def __init__(self, log: "SpanLog", name: str):
        self.log, self.name = log, name

    def __enter__(self):
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.log.add(self.name, dt)
        return False


class SpanLog:
    """Count and seconds per span name; safe under the prefetch worker."""

    def __init__(self):
        self._table: dict[str, list] = {}
        self._lock = threading.Lock()

    def __call__(self, name: str) -> _Span:
        return _Span(self, name)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            rec = self._table.get(name)
            if rec is None:
                self._table[name] = [1, seconds]
            else:
                rec[0] += 1
                rec[1] += seconds

    def n(self, name: str) -> int:
        return self._table.get(name, (0, 0.0))[0]

    def s(self, name: str) -> float:
        return self._table.get(name, (0, 0.0))[1]

    def as_dict(self) -> dict:
        with self._lock:
            return {k: {"n": n, "s": round(s, 6)}
                    for k, (n, s) in sorted(self._table.items())}


def no_span(name: str) -> contextlib.nullcontext:
    """Stand-in for a `SpanLog` where no runtime books the work."""
    return contextlib.nullcontext()


def segment_fn_name(ops: Iterable[str]) -> str:
    """Name of a segment closure, and so of its XLA module: ``ds_`` and
    the segment's op names in first-use order (``ds_add_eye_solve``),
    cut at a whole op within `FN_NAME_MAX` characters, with every
    character outside ``[A-Za-z0-9_]`` made ``_``."""
    name, seen = "ds", set()
    for op in ops:
        word = re.sub(r"[^A-Za-z0-9_]", "_", op)
        if word in seen:
            continue
        if len(name) + 1 + len(word) > FN_NAME_MAX:
            break
        seen.add(word)
        name += "_" + word
    return name

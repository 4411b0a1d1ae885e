"""Reduction of a JAX profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` that `jax.profiler` writes into plain
lists of events; the functions below it work on those lists alone, so
the tests can feed them recorded or hand-made events.

- Device planes are the planes named ``/device:TPU:<n>``. Their line
  ``XLA Ops`` holds one event per executed HLO op, and control-flow ops
  (``while``, ``conditional``) contain their bodies' ops as nested events
  on the same line. ``XLA Modules`` holds one event per program run.
- An op event's name is the HLO text (``%gram_pallas.1 = f32[...]
  custom-call(...)``); `op_name` keeps the instruction name before
  `` = `` and drops the ``%``.
- Host spans are the benchmark's own `jax.profiler.TraceAnnotation`s,
  on the host plane's threads, named ``bench.<what>``.
"""
from __future__ import annotations

import glob
import math
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Events of one traced window: op events per device id, program
    runs per device id, and the benchmark's host spans."""
    ops: dict[int, list[Event]] = field(default_factory=dict)
    modules: dict[int, list[Event]] = field(default_factory=dict)
    host_spans: list[Event] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name == OPS_LINE:
                    tr.ops[dev] = [Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == MODULES_LINE:
                    tr.modules[dev] = [Event(e.name, e.start_ns,
                                             e.duration_ns)
                                       for e in line.events]
            elif plane.name.startswith("/host"):
                tr.host_spans.extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    return tr


def op_name(name: str) -> str:
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def union(events: list[Event]) -> list[tuple[float, float]]:
    """Merged [start, end) intervals covered by the events."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in out]


def device_events(tr: Trace, dev: int) -> list[Event]:
    """Op events of a device, or its program runs where ops are absent."""
    return tr.ops.get(dev) or tr.modules.get(dev) or []


def clip(intervals, t0_ns: float, t1_ns: float):
    return [(max(a, t0_ns), min(b, t1_ns)) for a, b in intervals
            if b > t0_ns and a < t1_ns]


def busy_s(tr: Trace, t0_ns: float = -math.inf,
           t1_ns: float = math.inf) -> float:
    """Seconds of [t0, t1) in which an op ran, averaged over the traced
    devices."""
    devs = sorted(set(tr.ops) | set(tr.modules))
    if not devs:
        return 0.0
    return sum(b - a for d in devs for a, b in
               clip(union(device_events(tr, d)), t0_ns, t1_ns)
               ) / len(devs) / 1e9


def self_time_by_name(events: list[Event]) -> dict[str, float]:
    """Seconds per op name, each event less the nested events it holds,
    so a loop and its body are not counted twice."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [event, child time]

    def close(item):
        ev, child = item
        key = op_name(ev.name)
        out[key] = out.get(key, 0.0) + max(ev.dur_ns - child, 0.0) / 1e9

    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and e.start_ns >= stack[-1][0].end_ns:
            close(stack.pop())
        if stack:
            stack[-1][1] += e.dur_ns
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return out


def matching_s(tr: Trace, pattern) -> float:
    """Summed device seconds of op events whose name matches `pattern`
    (a regex on `op_name`), over all devices."""
    rx = re.compile(pattern)
    return sum(e.dur_ns for evs in tr.ops.values() for e in evs
               if rx.search(op_name(e.name))) / 1e9


def idle_gaps(tr: Trace, t0_ns: float, t1_ns: float, dev: int
              ) -> list[tuple[float, float]]:
    """Intervals of [t0, t1) in which no op ran on `dev`."""
    gaps, cur = [], t0_ns
    for a, b in union(device_events(tr, dev)):
        if a > cur:
            gaps.append((cur, min(a, t1_ns)))
        cur = max(cur, b)
    if cur < t1_ns:
        gaps.append((cur, t1_ns))
    return [(a, b) for a, b in gaps if b > a]


def span_at(spans: list[Event], t_ns: float,
            outside: str = "outside bench spans") -> str:
    """Name of the innermost host span covering `t_ns`, or `outside`."""
    best = None
    for s in spans:
        if s.start_ns <= t_ns < s.end_ns and (best is None
                                              or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else outside


def marked_window(tr: Trace, start: str, stop: str
                  ) -> tuple[float, float] | None:
    """The traced window: from the host span `start` to the span `stop`."""
    t0 = [s.start_ns for s in tr.host_spans if s.name == start]
    t1 = [s.start_ns for s in tr.host_spans if s.name == stop]
    if not t0 or not t1:
        return None
    return min(t0), max(t1)


def breakdown(tr: Trace, t0_ns: float, t1_ns: float, top: int = 10,
              outside: str = "outside bench spans") -> dict:
    """The ops in [t0, t1) with most self time, summed over devices, and
    the longest idle gaps of device 0 named by the host span that
    covered them (`outside` where the trace holds none: a span opened
    before the trace began is not in it)."""
    ops: dict[str, float] = {}
    for evs in tr.ops.values():
        inside = [e for e in evs if e.start_ns < t1_ns and e.end_ns > t0_ns]
        for k, v in self_time_by_name(inside).items():
            ops[k] = ops.get(k, 0.0) + v
    devs = sorted(set(tr.ops) | set(tr.modules))
    gaps = idle_gaps(tr, t0_ns, t1_ns, devs[0]) if devs else []
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[span_at(tr.host_spans, (a + b) / 2, outside),
                       (b - a) / 1e9]
                      for a, b in gaps[:top]],
    }

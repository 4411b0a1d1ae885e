"""General harness of the chip benchmark: one cell, one run.

`run.py` is the entry; this module does the work, driven by data:

- the cell (`BENCHMARK.json` `workloads`) names a configuration file,
  `configs/<config>.json`, and a traffic file, `traffic/<traffic>.json`;
- the traffic file's ``driver`` names the module that drives it,
  `drivers/<driver>.py`, whose ``Driver`` warms the cell, runs the
  window, traces and checks; a driver loads what else the files name
  (the job cells' `jobs/<job>.py` and `data/<generator>.py`);
- each per-layer metric is read by `metrics/<name>.py`, a module with
  ``read(ctx) -> float | None``;
- `reference.py` holds the plain float64 NumPy reference and the
  lower-precision control; `trace.py` reduces the profiler trace.

A run: find the chips (or fail), make the data from the seed, warm the
cell's own shapes, measure for ``--seconds``, compare what the timed
path produced with the reference, print the result line.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.abspath(__file__))


def load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# loaded by path: `trace` is also the name of a standard-library module
tracemod = load_module("bench_trace", os.path.join(BENCH, "trace.py"))
reference = load_module("bench_reference", os.path.join(BENCH, "reference.py"))
# --rehearse: tiny sizes on the CPU, for the tests only
REHEARSE_ROWS, REHEARSE_COLS = 4096, 64
TRACE_START, TRACE_STOP = "bench.trace_start", "bench.trace_stop"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


# -- cell lookup ----------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> SimpleNamespace:
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    bench = os.path.join(root, "bench")
    config = load_json(os.path.join(bench, "configs", cell["config"] + ".json"))
    traffic = load_json(os.path.join(bench, "traffic",
                                     cell["traffic"] + ".json"))
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return SimpleNamespace(name=name, cell=cell, config=config,
                           traffic=traffic, e2e=e2e, per_layer=layer,
                           bench=bench)


def load_reader(bench: str, metric: str):
    return load_module("bench_metric_" + metric.replace(".", "_").replace(
        "-", "_"), os.path.join(bench, "metrics", metric + ".py"))


def peaks_for(bench: str, kind: str) -> dict:
    table = load_json(os.path.join(bench, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


# -- compile events -----------------------------------------------------------

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """JAX's own compile-duration events: seconds and program compiles."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            if event == COMPILE_EVENTS[-1]:
                self.compiles += 1

    def snapshot(self) -> tuple[float, int]:
        return self.seconds, self.compiles


# names of the harness's spans open on the main thread, innermost last:
# a trace slice that starts inside a span does not record that span
OPEN_SPANS: list[str] = []


@contextlib.contextmanager
def span(name: str):
    import jax
    OPEN_SPANS.append(name)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        OPEN_SPANS.pop()


def load_driver(c: SimpleNamespace):
    kind = c.traffic["driver"]
    return load_module("bench_driver_" + kind,
                       os.path.join(c.bench, "drivers", kind + ".py")).Driver


# -- the run -----------------------------------------------------------------

def parse_args(argv):
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU, for the tests; nothing it "
                        "prints is a device number")
    p.add_argument("--control", action="store_true",
                   help="put the lower-precision reference in the "
                        "program's place (the control; not a benchmark run)")
    return p.parse_args(argv)


def find_devices(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def rehearsal_budget(rows: int, cols: int):
    """Scale the program's streaming budget (256 MB for the paper's 1e8
    values) with the rehearsal's data, so the tiny plan streams X in
    buckets as the full-size plan does on the chip."""
    from repro.core import costmodel
    old = costmodel.CHUNK_MEM_BUDGET
    costmodel.CHUNK_MEM_BUDGET = max(rows * cols * old // 10**8, 1 << 14)
    try:
        yield
    finally:
        costmodel.CHUNK_MEM_BUDGET = old


def main(argv=None, root: str | None = None,
         t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    root = root or os.path.dirname(BENCH)
    c = load_cell(root, args.workload)
    chips = int(c.cell["chips"])
    try:
        devs = find_devices(chips, args.rehearse)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    import jax
    import repro.core  # noqa: F401  (x64 and the program's settings)
    from repro.core import faults
    kind = devs[0].device_kind
    peaks = None if args.rehearse else peaks_for(c.bench, kind)
    log(f"cell {c.name}: {chips} x {devs[0].platform} {kind}"
        + ("  [CPU REHEARSAL: no number printed is a device number]"
           if args.rehearse else ""))
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)
    threads = max(1, min(16, (os.cpu_count() or 2) - 1))
    driver_cls = load_driver(c)

    budget = (rehearsal_budget(min(c.config["data"]["rows"], REHEARSE_ROWS),
                               min(c.config["data"]["cols"], REHEARSE_COLS))
        if args.rehearse else contextlib.nullcontext())
    with budget, faults.inject(None):
        driver = driver_cls(c, args, threads)
        log(f"driver: {driver.describe()}")
        driver.warm()
        setup_compile = meter.snapshot()
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        log(f"set-up: {setup_s:.3f} s, of it compile {setup_compile[0]:.3f} s"
            f" over {setup_compile[1]} programs")
        w = driver.run_window(args.seconds)
        tracer = Tracer() if args.trace else None
        traced = driver.trace(w, tracer) if tracer else None
        window_compiles = meter.snapshot()[1] - setup_compile[1]
        log(f"compiles inside the window: {window_compiles}")
        mem_peak = memory_peak(devs)
        e2e = driver.e2e(w)
        layer_ctx = None
        if tracer and not tracer.began:     # a slice that never began fails
            shutil.rmtree(tracer.dir, ignore_errors=True)
        elif tracer:
            layer_ctx = trace_context(c, driver, w, tracer, traced, peaks,
                                      chips, setup_compile, window_compiles)
        checks = driver.check(w)

    attempted, failed = w.attempted, len(w.failures)
    limits = {k: c.config["limits"][k] for k in checks}
    correct = (failed == 0 and window_compiles == 0 and bool(e2e)
               and bool(checks)
               and all(checks[k] <= v for k, v in limits.items()))
    metrics = {}
    units = {m["name"]: m["unit"] for m in c.e2e + c.per_layer}
    if layer_ctx is not None:
        for m in c.per_layer:
            v = load_reader(c.bench, m["name"]).read(layer_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    elif not args.trace:
        e2e["setup_s"] = setup_s
        for m in c.e2e:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": units[m["name"]]}
    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = "CPU rehearsal at tiny size: not device numbers"
    if layer_ctx is not None:
        device["busy_s"] = layer_ctx.busy_s
        device["window_s"] = layer_ctx.window_s
        result["breakdown"] = layer_ctx.breakdown
    result["checks"] = {k: {"value": checks[k], "limit": v}
                        for k, v in limits.items()}
    for k, v in limits.items():
        log(f"check {k} {checks[k]!r} limit {v!r} "
            f"{'ok' if checks[k] <= v else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


class Tracer:
    """The profiler over part of a run, between two marker spans; `start`
    and `stop` may come from another thread than the traced work."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._lock = threading.Lock()
        self._on = False
        self.began = False
        # the main thread's innermost harness span when the trace began
        self.started_in = "no bench span"

    def start(self, mark: bool = True):
        """Starts the profiler, and the traced window with it unless
        `mark` is false (then `mark_start` opens the window later)."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with self._lock:
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._on = True
            if OPEN_SPANS:
                self.started_in = OPEN_SPANS[-1]
        if mark:
            self.mark_start()

    def mark_start(self):
        import jax
        with self._lock:
            with jax.profiler.TraceAnnotation(TRACE_START):
                pass
            self.began = True

    def stop(self):
        import jax
        with self._lock:
            if self._on:
                with jax.profiler.TraceAnnotation(TRACE_STOP):
                    pass
                t = time.perf_counter()
                jax.profiler.stop_trace()
                self._on = False
                log(f"trace written in {time.perf_counter() - t:.3f} s")

    def read(self):
        try:
            return tracemod.load(tracemod.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def trace_context(c, driver, w, tracer, traced, peaks, chips, setup_compile,
                  window_compiles) -> SimpleNamespace:
    t = time.perf_counter()
    tr = tracer.read()
    win = tracemod.marked_window(tr, TRACE_START, TRACE_STOP)
    if win is None:
        raise RuntimeError("the trace holds no window markers")
    window_s = (win[1] - win[0]) / 1e9
    busy = tracemod.busy_s(tr, *win)
    jobs_s = (w.jobs[-1]["t1"] - w.jobs[0]["t0"]) if w.jobs else 0.0
    ctx = SimpleNamespace(
        cell=c.cell, config=c.config, traffic=c.traffic, chips=chips,
        peaks=peaks, trace=tr, tracemod=tracemod, window_s=window_s,
        busy_s=busy, jobs=w.jobs, jobs_s=jobs_s,
        traced=traced, traced_jobs=w.traced, window=w, driver=driver,
        setup_compile_s=setup_compile[0], window_compiles=window_compiles,
        breakdown=tracemod.breakdown(
            tr, *win, outside=f"{tracer.started_in} (open when the trace began)"))
    log(f"trace: {sum(len(v) for v in tr.ops.values())} op events on "
        f"{len(tr.ops)} device(s), window {window_s:.3f} s, busy "
        f"{busy:.3f} s, read in {time.perf_counter() - t:.3f} s")
    return ctx

"""Driver of job traffic: whole lifecycle jobs, back to back.

The traffic file names the job, `jobs/<job>.py`, which holds the entry
it calls, its reference, its control and its work formulas; the
configuration names the data generator, `data/<generator>.py`. Each job
makes a fresh runtime (with a fresh reuse cache where the traffic asks
for one) and binds its leaves anew from the host arrays, so no result
of one job can serve the next.

Under `--trace 1` one more job runs after the window under the
profiler: the whole job, or the part of it between two counts of its
reuse cache's probes (`trace_probes` in the traffic file), which cuts
the slice on the program's own progress.
"""
from __future__ import annotations

import math
import os
import threading
import time
from types import SimpleNamespace

import harness
from harness import log, reference, span

PROBE_POLL_S = 0.0005


class ProbeSlice:
    """Traces from `count()` reaching `begin` to its reaching `end`, from a
    polling thread. The profiler starts `end - begin` counts earlier, so
    that its own start-up (about 0.1 s on a v5e) falls outside the
    window."""

    def __init__(self, tracer, count, begin: int, end: int):
        self.tracer, self.count = tracer, count
        self.begin, self.end = begin, end
        self.began = self.ended = False
        self._quit = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _wait_for(self, n: int) -> bool:
        while self.count() < n:
            if self._quit.wait(PROBE_POLL_S):
                return False
        return True

    def _poll(self):
        if not self._wait_for(2 * self.begin - self.end):
            return
        self.tracer.start(mark=False)
        if self._wait_for(self.begin):
            self.tracer.mark_start()
            self.began = True
            if self._wait_for(self.end):
                self.tracer.stop()
                self.ended = True

    def start(self) -> "ProbeSlice":
        self._thread.start()
        return self

    def finish(self) -> str | None:
        """Ends the polling; what went wrong, if anything."""
        self._quit.set()
        self._thread.join()
        self.tracer.stop()
        if not self.ended:
            return (f"the trace slice at probes {self.begin}-{self.end} "
                    f"{'did not end' if self.began else 'never began'} "
                    f"({self.count()} probes)")
        return None


class Driver:

    def __init__(self, c: SimpleNamespace, args, threads: int):
        d = c.config["data"]
        self.rows, self.cols = d["rows"], d["cols"]
        if args.rehearse:
            self.rows = min(self.rows, harness.REHEARSE_ROWS)
            self.cols = min(self.cols, harness.REHEARSE_COLS)
        self.c, self.args, self.traffic = c, args, c.traffic
        self.job_name = c.traffic["job"]
        self.job_mod = harness.load_module(
            "bench_job_" + self.job_name,
            os.path.join(c.bench, "jobs", self.job_name + ".py"))
        gen = harness.load_module(
            "bench_data_" + d["generator"],
            os.path.join(c.bench, "data", d["generator"] + ".py"))
        t = time.perf_counter()
        self.data = gen.make(args.seed, d, self.rows, self.cols, threads)
        log(f"data: {self.rows}x{self.cols} "
            f"({sum(a.nbytes for a in self.data.values()) / 1e9:.3f} GB) "
            f"in {time.perf_counter() - t:.3f} s")
        self.live = None        # the running job's runtime

    def describe(self) -> str:
        return f"{self.job_name} over {self.rows}x{self.cols}"

    def warm(self):
        self.job()      # one whole job compiles every program the cell runs

    def job(self) -> dict:
        from repro.core import LineageRuntime, ReuseCache, input_tensor
        t0 = time.perf_counter()
        with span("bench.job"):
            if self.args.control:
                out = self.job_mod.control(self.data, self.traffic)
                return dict(t0=t0, t1=time.perf_counter(), out=out,
                            counters=None)
            rt = LineageRuntime(cache=ReuseCache()
                                if self.traffic.get("reuse_cache") else None)
            self.live = rt
            with span("bench.bind"):
                leaves = {k: input_tensor(k, v) for k, v in self.data.items()}
            with span("bench.call." + self.job_name):
                out = self.job_mod.run(leaves, self.traffic, rt)
            self.live = None
        counters = rt.stats.as_dict()
        if rt.cache is not None:
            counters["reuse"] = rt.cache.stats.as_dict()
        return dict(t0=t0, t1=time.perf_counter(), out=out,
                    counters=counters)

    def probes(self) -> int:
        rt = self.live
        return rt.cache.stats.probes if rt is not None and rt.cache else 0

    def computed(self, job: dict) -> list[str]:
        """What the job's own counters say it failed to compute."""
        cnt = job["counters"]
        if cnt is None:        # the control computes in the harness
            return []
        bad = []
        if cnt["executed"] <= 0:
            bad.append("no instruction executed")
        expect = self.c.config.get("expect", {})
        st = cnt.get("streaming", {})
        if expect.get("streamed") and not (
                st.get("chunks", 0) > 0 and st.get("chunks_reused", 0) == 0):
            bad.append(f"X was not streamed afresh ({st})")
        return bad

    def _checked_job(self, failures: list) -> dict | None:
        try:
            j = self.job()
        except Exception as e:  # a job that fails counts as failed
            log(f"job failed: {type(e).__name__}: {e}")
            failures.append(repr(e))
            return None
        log(f"job: {j['t1'] - j['t0']:.3f} s")
        bad = self.computed(j)
        if bad:
            failures.append("; ".join(bad))
        return j

    def run_window(self, seconds: float) -> SimpleNamespace:
        """Whole jobs back to back; none starts after `seconds`."""
        jobs, failures = [], []
        t_end = time.perf_counter() + seconds
        while True:
            j = self._checked_job(failures)
            if j is None:
                break
            jobs.append(j)
            if time.perf_counter() >= t_end:
                break
        return SimpleNamespace(jobs=jobs, failures=failures, traced=[],
                               attempted=len(jobs) + (j is None))

    def trace(self, w: SimpleNamespace, tracer) -> str:
        """One more job after the window, under the profiler; returns
        what the trace covers. A rehearsal's tiny job is traced whole."""
        probes = None if self.args.rehearse \
            else self.traffic.get("trace_probes")
        sl = ProbeSlice(tracer, self.probes, *probes).start() if probes \
            else None
        if sl is None:
            tracer.start()
        j = self._checked_job(w.failures)
        if sl is None:
            tracer.stop()
        else:
            err = sl.finish()
            if err:
                w.failures.append(err)
        w.attempted += 1
        if j is not None:
            w.traced = [j]
        return f"probes {probes[0]}-{probes[1]}" if probes else "job"

    def e2e(self, w: SimpleNamespace) -> dict:
        if not w.jobs:
            return {}
        span_s = w.jobs[-1]["t1"] - w.jobs[0]["t0"]
        return {"job_s": span_s / len(w.jobs)}

    def check(self, w: SimpleNamespace) -> dict:
        """Every timed job's outputs against the float64 NumPy reference
        on the same data; each number is the worst over the jobs."""
        t = time.perf_counter()
        ref = self.job_mod.expected(self.data, self.traffic)
        checks: dict[str, float] = {}
        for j in w.jobs + w.traced:
            for k, v in self.job_mod.compare(j["out"], ref).items():
                v = v if v == v else math.inf       # NaN fails
                checks[k] = max(checks.get(k, -math.inf), v)
        if not checks:      # no job finished: every number fails
            checks = {k: math.inf for k in self.job_mod.compare(ref, ref)}
        log(f"reference: {time.perf_counter() - t:.3f} s")
        return checks


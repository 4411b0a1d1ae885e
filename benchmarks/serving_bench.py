"""Low-latency scoring: coalesced serving vs sequential single-request.

ISSUE 7: a trained lmDS-style scoring plan deployed behind
`repro.serving.ModelServer`:

  * **closed-loop throughput** — 8 concurrent clients scoring through
    the server (requests coalesce onto warm vmapped buckets) vs the
    same request stream scored one-at-a-time through the solo
    `PreparedScript` path; the coalesced path must sustain >= 3x.
  * **open-loop latency** — a seeded Poisson arrival process at several
    offered rates; per-request p50/p99 latency and sustained QPS.

Asserts zero hot-path retraces after deploy-time warmup
(`RuntimeStats.serving.retraces`) and bitwise parity between coalesced
and sequential scoring (single-row requests — see tests/test_serving.py
for why single-row contractions are the bitwise-stable serving shape).

Appends a trajectory entry to ``benchmarks/BENCH_serving.json``.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from .common import COLS, emit

BENCH_JSON = os.path.join(os.path.dirname(__file__), "BENCH_serving.json")


def _make_script(d: int, rt, rng):
    from repro.core import input_tensor, ops
    from repro.core.runtime import PreparedScript

    beta = input_tensor("srv_beta", rng.normal(size=(d, 1)))

    def scoring(x):
        return ops.matmul(x, beta)

    return PreparedScript(scoring, [(1, d)], runtime=rt)


def _closed_loop(server, script, rows, concurrency: int) -> dict:
    """Closed-loop at offered concurrency `concurrency`: a pipelining
    client keeps that many requests in flight (`ModelServer.submit` /
    `ScoreFuture.result`, the event-loop client shape) vs the same
    stream scored one-at-a-time through the solo `PreparedScript`."""
    from collections import deque

    n = len(rows)
    # sequential baseline: one request at a time, no coalescing
    t0 = time.perf_counter()
    seq = [script(x) for x in rows]
    t_seq = time.perf_counter() - t0

    results: list = [None] * n
    outstanding: deque = deque()
    t0 = time.perf_counter()
    i = 0
    while i < n or outstanding:
        while i < n and len(outstanding) < concurrency:
            outstanding.append((i, server.submit(rows[i])))
            i += 1
        j, fut = outstanding.popleft()
        results[j] = fut.result()
    t_coal = time.perf_counter() - t0

    for got, ref in zip(results, seq):      # exact output parity
        for a, b in zip(got, ref):
            assert (a == b).all(), "coalesced != sequential scoring"
    return dict(n=n,
                sequential_qps=n / t_seq,
                coalesced_qps=n / t_coal,
                sequential_us_per_call=t_seq / n * 1e6,
                coalesced_us_per_call=t_coal / n * 1e6,
                speedup=t_seq / t_coal)


def _open_loop(server, d: int, rate_qps: float, n: int, seed: int) -> dict:
    """Seeded-Poisson open-loop load: one thread per request fires at
    its scheduled arrival regardless of completions (no coordinated
    omission); reports per-request latency percentiles, sustained QPS
    over the span from first arrival to last completion, and queue-idle
    time — span minus dispatch-stage busy seconds (the runtime's
    `ds.serve.batch` span), i.e. how much headroom the request path
    still has at this offered rate."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_qps, size=n)
    arrivals = np.cumsum(gaps)
    rows = [rng.normal(size=(1, d)) for _ in range(n)]
    lat_us = [0.0] * n
    done_at = [0.0] * n
    spans = server.runtime.stats.spans
    busy0 = spans.s("ds.serve.batch")
    start = time.perf_counter() + 0.05   # common epoch for all threads

    def fire(i):
        delay = start + arrivals[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t0 = time.perf_counter()
        server.score(rows[i])
        t1 = time.perf_counter()
        lat_us[i] = (t1 - t0) * 1e6
        done_at[i] = t1

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    span = max(done_at) - (start + float(arrivals[0]))
    p50, p99 = np.percentile(lat_us, [50, 99])
    busy = spans.s("ds.serve.batch") - busy0
    idle = max(span - busy, 0.0)
    return dict(rate=rate_qps, n=n, p50_us=float(p50), p99_us=float(p99),
                qps=n / span, busy_s=float(busy),
                queue_idle_s=float(idle),
                idle_frac=float(idle / span) if span > 0 else 0.0)


def main(d: int = COLS, n: int = 512, concurrency: int = 16,
         max_batch: int = 16, rates=(500.0, 2000.0),
         openloop_n: int = 200) -> dict:
    from repro.core import LineageRuntime, clear_jit_cache
    from repro.serving import ModelServer

    clear_jit_cache()
    rng = np.random.default_rng(7)
    rt = LineageRuntime()
    script = _make_script(d, rt, rng)
    rows = [rng.normal(size=(1, d)) for _ in range(n)]

    server = ModelServer(script, runtime=rt, max_batch=max_batch,
                         max_wait_us=2000.0)
    t0 = time.perf_counter()
    server.deploy()
    t_deploy = time.perf_counter() - t0

    closed = _closed_loop(server, script, rows, concurrency)
    open_runs = [_open_loop(server, d, r, openloop_n, seed=int(r))
                 for r in rates]

    log = rt.stats.serving
    assert log.retraces == 0, \
        f"hot path recompiled {log.retraces}x after deploy warmup"
    assert closed["speedup"] >= 3.0, \
        f"coalesced throughput only {closed['speedup']:.2f}x sequential " \
        f"at concurrency {concurrency} (>= 3x required)"

    emit("serving_coalesced", closed["coalesced_us_per_call"] * 1e-6,
         f"seq_us={closed['sequential_us_per_call']:.1f};"
         f"conc={concurrency};speedup={closed['speedup']:.2f}x")
    for runm in open_runs:
        emit(f"serving_openloop_{int(runm['rate'])}qps",
             runm["p50_us"] * 1e-6,
             f"p99_us={runm['p99_us']:.0f};qps={runm['qps']:.0f};"
             f"idle_frac={runm['idle_frac']:.2f}")

    entry = dict(
        benchmark="serving_coalesce",
        workload=f"score (1x{d})@({d}x1), conc={concurrency}, "
                 f"max_batch={max_batch}",
        deploy_warmup_us_per_call=round(t_deploy * 1e6, 1),
        sequential_us_per_call=round(closed["sequential_us_per_call"], 1),
        coalesced_us_per_call=round(closed["coalesced_us_per_call"], 1),
        speedup=round(closed["speedup"], 2),
        sequential_qps=round(closed["sequential_qps"], 1),
        coalesced_qps=round(closed["coalesced_qps"], 1),
        retraces=int(log.retraces),
        mean_coalesce=round(log.requests / max(log.batches, 1), 2),
        parity="bitwise",
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
    )
    for runm in open_runs:      # flattened latency columns (aggregate())
        tag = f"load{int(runm['rate'])}"
        entry[f"{tag}_p50_us"] = round(runm["p50_us"], 1)
        entry[f"{tag}_p99_us"] = round(runm["p99_us"], 1)
        entry[f"{tag}_qps"] = round(runm["qps"], 1)
        entry[f"{tag}_idle_frac"] = round(runm["idle_frac"], 3)

    server.shutdown()

    trajectory = []
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                trajectory = json.load(f)
        except Exception:
            trajectory = []
    trajectory.append(entry)
    with open(BENCH_JSON, "w") as f:
        json.dump(trajectory, f, indent=2)
    return entry


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    print("name,us_per_call,derived")
    if "--smoke" in sys.argv:
        out = main(d=64, n=256, concurrency=8, max_batch=8,
                   rates=(500.0, 1000.0), openloop_n=120)
    else:
        out = main()
    print(json.dumps(out, indent=2))

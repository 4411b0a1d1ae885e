"""Async pipelined dispatch (PR 9) vs the synchronous PR-8 runtime.

Two lanes, both comparing ``REPRO_PIPELINE_DEPTH=1`` (the exact PR-8
synchronous executor) against depth 2 (async dispatch + donation +
chunk prefetch + serving rebatching) in one process — the depth is read
per plan run, so both modes share warm jit executables where their keys
coincide:

  * **streamed append-retrain** — warm incremental retrain of lmDS
    after a 10% row append under a 10x-undersized memory budget. The
    chunk-cache keys are bitwise identical across depths (the pipelined
    loop derives bucket fingerprints from the leaf's block-sum table
    instead of re-hashing every slice), so the warm lane measures the
    same cache hits minus the removed fingerprint pass; depth 2 must
    be >= `min_speedup` faster, with results equal to 1e-10, zero
    timed-lane retraces, and `peak_live_bytes` (charging BOTH in-flight
    buckets) within the budget.
  * **serving sustained QPS** — the scoring server under seeded-Poisson
    open-loop load with continuous rebatching on; must sustain
    >= `qps_floor` (the PR-7 closed baseline) with zero hot-path
    retraces, and single-row results bitwise across depths.

Appends a trajectory entry to ``benchmarks/BENCH_pipeline.json``.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import emit

BENCH_JSON = os.path.join(os.path.dirname(__file__), "BENCH_pipeline.json")


def _lm_ref(Xh, yh, reg=1e-3):
    return np.linalg.solve(Xh.T @ Xh + reg * np.eye(Xh.shape[1]),
                           Xh.T @ yh)


def _lm_run(rt, Xh, yh, reg=1e-3):
    from repro.core.dag import input_tensor
    from repro.lifecycle.regression import lmDS
    X = input_tensor("X", Xh)
    y = input_tensor("y", yh)
    return np.asarray(lmDS(X, y, reg=reg, runtime=rt)).ravel()


def _append_lane(rows: int, cols: int, budget_ratio: int, repeats: int,
                 min_speedup: float) -> dict:
    from repro.core import costmodel
    from repro.core.jit_cache import get_jit_cache
    from repro.core.reuse import ReuseCache
    from repro.core.runtime import LineageRuntime

    rng = np.random.default_rng(9)
    Xh = rng.normal(size=(rows, cols))
    yh = rng.normal(size=(rows,))
    extra = rows // 10
    arng = np.random.default_rng(109)
    Xa = np.vstack([Xh, arng.normal(size=(extra, cols))])
    ya = np.concatenate([yh, arng.normal(size=(extra,))])
    ref = _lm_ref(Xa, ya).ravel()
    budget = int(Xh.nbytes // budget_ratio)
    jstats = get_jit_cache().stats

    saved_budget = costmodel.CHUNK_MEM_BUDGET
    out: dict = {}
    try:
        costmodel.CHUNK_MEM_BUDGET = budget
        for depth in ("1", "2"):
            os.environ["REPRO_PIPELINE_DEPTH"] = depth
            # unmeasured warm cycle per depth: compiles this depth's
            # executables (depth 2 adds |don:-keyed variants) so the
            # timed lane is pure steady state
            wrt = LineageRuntime(cache=ReuseCache(), fuse=True)
            _lm_run(wrt, Xh, yh)
            _lm_run(wrt, Xa, ya)
            ts = []
            for _ in range(repeats):
                rt = LineageRuntime(cache=ReuseCache(), fuse=True)
                _lm_run(rt, Xh, yh)        # base training populates
                s = rt.stats.streaming     # the chunk-partial cache
                b_chunks = s.chunks
                miss0 = jstats.misses
                t0 = time.perf_counter()
                got = _lm_run(rt, Xa, ya)
                ts.append(time.perf_counter() - t0)
                retraces = jstats.misses - miss0
                assert retraces == 0, \
                    f"depth {depth}: {retraces} timed-lane retraces"
                assert np.abs(got - ref).max() < 1e-10
                assert s.chunks_reused == b_chunks, \
                    "append shifted existing chunk boundaries"
                assert 0 < s.peak_live_bytes <= budget, \
                    f"depth {depth}: live {s.peak_live_bytes} > {budget}"
            out[depth] = dict(t=float(np.median(ts)), rt=rt)
        p = out["2"]["rt"].stats.pipeline
        assert p.prefetch_issued > 0, "prefetch never engaged"
        assert out["1"]["rt"].stats.pipeline.total == 0
    finally:
        costmodel.CHUNK_MEM_BUDGET = saved_budget
        os.environ.pop("REPRO_PIPELINE_DEPTH", None)

    t_sync, t_pipe = out["1"]["t"], out["2"]["t"]
    speedup = t_sync / t_pipe
    assert speedup >= min_speedup, \
        f"pipelined append-retrain only {speedup:.2f}x over the " \
        f"synchronous path (>= {min_speedup}x required)"
    pdict = p.as_dict()
    return dict(budget=budget, t_sync=t_sync, t_pipe=t_pipe,
                speedup=speedup,
                prefetch_issued=pdict["prefetch_issued"],
                prefetch_hits=pdict["prefetch_hits"],
                donated_buffers=pdict["donated_buffers"],
                peak_live_bytes=int(
                    out["2"]["rt"].stats.streaming.peak_live_bytes))


def _serving_lane(d: int, rate: float, openloop_n: int,
                  qps_floor: float) -> dict:
    from repro.core import LineageRuntime
    from repro.serving import ModelServer
    from benchmarks.serving_bench import _make_script, _open_loop

    rng = np.random.default_rng(11)
    probe_rows = [rng.normal(size=(1, d)) for _ in range(32)]
    got = {}
    try:
        for depth in ("1", "2"):
            os.environ["REPRO_PIPELINE_DEPTH"] = depth
            rt = LineageRuntime()
            script = _make_script(d, rt, np.random.default_rng(7))
            with ModelServer(script, runtime=rt, max_batch=16,
                             max_wait_us=2000.0) as server:
                got[depth] = [server.score(x)[0] for x in probe_rows]
                if depth == "2":
                    run = _open_loop(server, d, rate, openloop_n,
                                     seed=int(rate))
                    log = rt.stats.serving
                    assert log.retraces == 0, \
                        f"hot path recompiled {log.retraces}x"
                    rebatches = rt.stats.pipeline.rebatches
    finally:
        os.environ.pop("REPRO_PIPELINE_DEPTH", None)
    for a, b in zip(got["1"], got["2"], strict=True):
        assert np.array_equal(a, b), \
            "depth-2 serving diverged from the synchronous dispatcher"
    assert run["qps"] >= qps_floor, \
        f"sustained {run['qps']:.0f} qps with rebatching " \
        f"(>= {qps_floor:.0f} required)"
    assert rebatches > 0, "rebatching never overlapped a batch"
    return dict(run=run, rebatches=int(rebatches))


def main(rows: int = 131072, cols: int = 256, budget_ratio: int = 10,
         repeats: int = 3, min_speedup: float = 1.15,
         d: int = 256, rate: float = 3000.0, openloop_n: int = 600,
         qps_floor: float = 2105.0) -> dict:
    from repro.core import clear_jit_cache

    clear_jit_cache()
    app = _append_lane(rows, cols, budget_ratio, repeats, min_speedup)
    srv = _serving_lane(d, rate, openloop_n, qps_floor)

    emit("pipeline_append_retrain", app["t_pipe"],
         f"sync_us={app['t_sync']*1e6:.0f};"
         f"speedup={app['speedup']:.2f}x")
    emit("pipeline_serving_openloop", srv["run"]["p50_us"] * 1e-6,
         f"qps={srv['run']['qps']:.0f};rebatches={srv['rebatches']};"
         f"idle_frac={srv['run']['idle_frac']:.2f}")

    entry = dict(
        benchmark="pipeline_async",
        workload=f"lmDS append {rows}x{cols} budget=nbytes/"
                 f"{budget_ratio}; serve (1x{d}) @ {rate:.0f}qps",
        budget_bytes=app["budget"],
        append_sync_us_per_call=round(app["t_sync"] * 1e6, 1),
        append_pipelined_us_per_call=round(app["t_pipe"] * 1e6, 1),
        append_speedup=round(app["speedup"], 2),
        prefetch_issued=app["prefetch_issued"],
        prefetch_hits=app["prefetch_hits"],
        donated_buffers=app["donated_buffers"],
        peak_live_bytes=app["peak_live_bytes"],
        serving_qps=round(srv["run"]["qps"], 1),
        serving_p50_us=round(srv["run"]["p50_us"], 1),
        serving_p99_us=round(srv["run"]["p99_us"], 1),
        serving_idle_frac=round(srv["run"]["idle_frac"], 3),
        rebatches=srv["rebatches"],
        retraces=0,
        parity="bitwise (serving), 1e-10 (streamed lmDS)",
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
    )
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
        # smaller matrix + relaxed floors on shared CI cores; the full
        # run holds the >= 1.15x / >= 2105 qps acceptance bars
        out = main(rows=16384, repeats=2, min_speedup=1.05,
                   d=64, rate=2600.0, openloop_n=300, qps_floor=1200.0)
    else:
        out = main()
    print(json.dumps(out, indent=2))

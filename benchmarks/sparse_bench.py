"""Sparsity- and reuse-aware fused execution benchmark (ISSUE 3).

Two paper-headline workloads that used to defeat the segment engine:

  * sparse lmDS — ridge regression over a density-0.05 design matrix.
    With compile-time format assignment the whole plan (BCOO gram/xtv +
    dense solve) traces into fused jit segments; compared against the
    per-instruction interpreter on the same BCOO kernels, and against
    the dense fused path.
  * reuse-enabled HPO — a lambda grid with an active `ReuseCache`.
    Cost-gated probe points keep segments multi-instruction (the Fig. 7
    scenario finally fuses) while reuse hit counts stay identical to
    the interpreter.

Appends a trajectory entry to ``benchmarks/BENCH_sparse.json``.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import emit, timed

BENCH_JSON = os.path.join(os.path.dirname(__file__), "BENCH_sparse.json")

DENSITY = 0.05


def _ridge(x, y, lam=0.1):
    from repro.core import ops
    n = x.shape[1]
    return ops.solve(ops.gram(x) + float(lam) * ops.eye(n), ops.xtv(x, y))


def _sparse_pipeline(a, b):
    """Sparse lmDS with a sparsity-preserving feature transform and
    training diagnostics: the transform chain stays BCOO end-to-end
    (format propagation), gram/xtv run the sparse kernels, and the tail
    is dense — one fused plan instead of a dozen eager BCOO dispatches.
    """
    from repro.core import ops
    xt = ops.sqrt(ops.abs_(a)) * 0.5
    n = xt.shape[1]
    beta = ops.solve(ops.gram(xt) + 0.1 * ops.eye(n), ops.xtv(xt, b))
    err = xt @ beta - b
    return beta, ops.sum_(err * err), \
        ops.cbind(ops.colSums(err), ops.colMaxs(err))


def _sparse_data(rows, cols, rng):
    x = rng.normal(size=(rows, cols)) * (rng.random((rows, cols)) < DENSITY)
    y = rng.normal(size=(rows, 1))
    return x, y


def _run_mode(fuse: bool, sparse: bool, xn, yn, calls: int):
    from repro.core import LineageRuntime, PreparedScript
    rt = LineageRuntime(fuse=fuse, sparse_inputs=sparse)
    ps = PreparedScript(_sparse_pipeline, [xn.shape, yn.shape], runtime=rt,
                        arg_sparsities=[DENSITY, 1.0])
    ps(xn, yn)  # warm: trace/compile outside the timed loop
    def loop():
        out = None
        for _ in range(calls):
            out = ps(xn, yn)
        return out
    return ps, loop


def _reuse_fusion(fuse: bool, xn, yn, lambdas):
    """Grid-search HPO with an active cache; returns (stats, cache stats,
    per-plan segmentation shape)."""
    from repro.core import LineageRuntime, ReuseCache, input_tensor
    from repro.core.compiler import compile_plan
    rt = LineageRuntime(cache=ReuseCache(), fuse=fuse)
    x, y = input_tensor("sbX", xn), input_tensor("sby", yn)
    for lam in lambdas:
        rt.evaluate([_ridge(x, y, lam)])
    plan = compile_plan([_ridge(x, y, lambdas[0])], reuse_enabled=True)
    segs = plan.segments_for(True)
    seg_shape = dict(
        instruction_count=len(plan.instructions),
        segment_count=len(segs),
        multi_instruction_segments=sum(1 for s in segs if s.fused),
        max_segment_ops=max(len(s.instructions) for s in segs))
    return rt.stats.as_dict(), rt.cache.stats.as_dict(), seg_shape


def main(rows: int = 1024, cols: int = 64, calls: int = 20,
         repeats: int = 3) -> dict:
    rng = np.random.default_rng(11)
    xn, yn = _sparse_data(rows, cols, rng)

    ps_fused, loop_fused = _run_mode(True, True, xn, yn, calls)
    ps_interp, loop_interp = _run_mode(False, True, xn, yn, calls)
    ps_dense, loop_dense = _run_mode(True, False, xn, yn, calls)

    t_fused = timed(loop_fused, repeats=repeats)
    t_interp = timed(loop_interp, repeats=repeats)
    t_dense = timed(loop_dense, repeats=repeats)

    out_f = ps_fused(xn, yn)
    out_i = ps_interp(xn, yn)
    out_d = ps_dense(xn, yn)  # dense fused path is the reference
    parity = max(float(np.max(np.abs(a - d)))
                 for outs in (out_f, out_i)
                 for a, d in zip(outs, out_d))
    # f64 XLA kernels off-TPU; the TPU Pallas paths (dense gram AND
    # block-sparse spmm) accumulate in f32 with different block orders
    import jax
    tol = 1e-4 if jax.default_backend() == "tpu" else 1e-8
    assert parity < tol, f"sparse paths diverge (max abs err {parity})"

    speedup_vs_interp = t_interp / max(t_fused, 1e-12)
    speedup_vs_dense = t_dense / max(t_fused, 1e-12)
    emit("sparse_fused_vs_interpreted", t_fused / calls,
         f"interp_us={t_interp / calls * 1e6:.1f};"
         f"speedup={speedup_vs_interp:.2f}x;"
         f"vs_dense={speedup_vs_dense:.2f}x")

    # reuse-enabled HPO: fused must keep multi-instruction segments and
    # the interpreter's exact hit behaviour
    lambdas = (0.1, 1.0, 10.0)
    rs_f, rc_f, shape = _reuse_fusion(True, xn, yn, lambdas)
    rs_i, rc_i, _ = _reuse_fusion(False, xn, yn, lambdas)
    hits_f = (rc_f["probes"], rc_f["hits"], rc_f["misses"])
    hits_i = (rc_i["probes"], rc_i["hits"], rc_i["misses"])
    assert hits_f == hits_i, \
        f"fused reuse diverged from interpreter: {hits_f} vs {hits_i}"
    assert shape["instruction_count"] > 2 * shape["segment_count"], \
        f"reuse-active plan failed to fuse: {shape}"
    # host seconds dispatching and waiting on the fused segments
    sp = rs_f["spans"]
    run_s = sum(sp.get(k, {}).get("s", 0.0)
                for k in ("ds.dispatch", "ds.wait"))
    emit("sparse_reuse_fusion", run_s / max(rs_f["segments"], 1),
         f"instr={shape['instruction_count']};"
         f"segments={shape['segment_count']};hits={rc_f['hits']}")

    entry = dict(
        benchmark="sparse_fused_vs_interpreted",
        workload=f"sparse_lmDS_pipeline({rows}x{cols}, density={DENSITY}, "
                 f"{calls} calls)",
        fused_sparse_us_per_call=round(t_fused / calls * 1e6, 1),
        interpreted_sparse_us_per_call=round(t_interp / calls * 1e6, 1),
        dense_fused_us_per_call=round(t_dense / calls * 1e6, 1),
        speedup_fused_vs_interpreted=round(speedup_vs_interp, 2),
        speedup_fused_vs_dense=round(speedup_vs_dense, 2),
        parity_max_abs_err=parity,
        reuse_fusion=dict(
            **shape,
            probes_hits_misses_fused=list(hits_f),
            probes_hits_misses_interpreted=list(hits_i),
            runtime_stats_fused=rs_f),
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
    print("name,us_per_call,derived")
    print(json.dumps(main(), indent=2))

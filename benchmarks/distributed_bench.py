"""Multi-device sharded execution vs single-device local execution.

ISSUE 6: the SystemDS distributed backend as a compiler placement —
`lower_distributed` shards large row-partitionable leaves over the
mesh's `data` axis and lowers partial reductions to per-shard compute
+ `psum` inside `shard_map`-compiled segments; `parfor(mode='shard')`
splits the HPO grid's bucket axis over the `config` axis.

Two measurements, both against the same fused local baseline:

  * **lmDS data-parallel** — one lmDS plan on an 8-device host mesh
    (`use_mesh(data=8)`) vs the local plan;
  * **grid config-parallel** — `grid_search_lm(mode='shard')` on
    `use_mesh(config=8)` vs the single-device vmapped grid.

`allclose` parity against the local path is asserted for both — the
hard invariant. Wall-clock speedup is recorded honestly: on a
single-core container the 8 "devices" share one core, so the
interesting signal is parity + collective accounting, not throughput
(real meshes get real scaling; the cost model's ICI terms are what
the compiler arbitrates with).

The measurement runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so it works
no matter how the parent process initialized jax. The child is a
rehearsal on a forced CPU mesh by design, so it runs with
``JAX_PLATFORMS=cpu``: on a TPU host the parent already holds the
chip, and a child that asked for it would wait or fail. Appends a
trajectory entry to ``benchmarks/BENCH_distributed.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from .common import emit

BENCH_JSON = os.path.join(os.path.dirname(__file__),
                          "BENCH_distributed.json")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICES = 8
_MARK = "RESULT "


def _child(rows: int, cols: int, k: int, repeats: int) -> None:
    """Runs inside the forced-8-device subprocess; prints one marked
    JSON line with the raw measurements."""
    import numpy as np

    from repro.core import LineageRuntime, clear_jit_cache, input_tensor, ops
    from repro.core.compiler import compile_plan
    from repro.distributed import use_mesh
    from repro.lifecycle.validation import grid_search_lm

    from .common import timed

    import jax
    assert jax.device_count() >= DEVICES, jax.device_count()

    rng = np.random.default_rng(17)
    xn = rng.normal(size=(rows, cols))
    yn = rng.normal(size=(rows, 1))

    def lmds(X, y):
        A = ops.gram(X) + 1e-3 * ops.eye(cols)
        beta = ops.solve(A, ops.xtv(X, y))
        resid = y - X @ beta
        return beta, ops.sum_(resid * resid)

    # --- lmDS: local fused baseline vs data-sharded -------------------
    clear_jit_cache()
    plan_lo = compile_plan(list(lmds(input_tensor("dbX", xn),
                                     input_tensor("dby", yn))))
    with use_mesh(data=DEVICES):
        plan_sh = compile_plan(list(lmds(input_tensor("dbX2", xn),
                                         input_tensor("dby2", yn))))
    rt_lo, rt_sh = LineageRuntime(), LineageRuntime()
    out_lo = rt_lo.run_plan(plan_lo)
    out_sh = rt_sh.run_plan(plan_sh)
    assert rt_sh.stats.shard.sharded_segments > 0, "plan did not shard"
    parity = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(out_sh, out_lo))
    for a, b in zip(out_sh, out_lo):
        np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-10)
    t_lo = timed(lambda: rt_lo.run_plan(plan_lo), repeats=repeats,
                 warmup=1)
    t_sh = timed(lambda: rt_sh.run_plan(plan_sh), repeats=repeats,
                 warmup=1)

    # --- grid: single-device vmap vs config-sharded -------------------
    lambdas = [float(10.0 ** (i / 4 - 2)) for i in range(k)]

    def grid(mode):
        rt = LineageRuntime()
        X = input_tensor(f"dbgX_{mode}", xn)
        y = input_tensor(f"dbgy_{mode}", yn)
        out = grid_search_lm(X, y, lambdas, runtime=rt, mode=mode)
        return out, rt

    (b_v, l_v), _ = grid("vmap")
    with use_mesh(data=1, config=DEVICES):
        (b_c, l_c), rt_c = grid("shard")
        assert rt_c.stats.shard.config_sharded_segments > 0
        t_grid_sh = timed(lambda: grid("shard"), repeats=repeats)
    np.testing.assert_allclose(b_c, b_v, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(l_c, l_v, rtol=1e-8)
    t_grid_v = timed(lambda: grid("vmap"), repeats=repeats)
    grid_parity = float(np.max(np.abs(b_c - b_v)))

    print(_MARK + json.dumps(dict(
        devices=DEVICES,
        local_s=t_lo, sharded_s=t_sh,
        parity_max_abs_err=parity,
        shard_meter=rt_sh.stats.shard.as_dict(),
        grid_vmap_s=t_grid_v, grid_shard_s=t_grid_sh,
        grid_parity_max_abs_err=grid_parity,
    )))


def main(rows: int = 32768, cols: int = 128, k: int = 16,
         repeats: int = 3) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.distributed_bench",
         "--child", str(rows), str(cols), str(k), str(repeats)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=560)
    if proc.returncode != 0:
        raise RuntimeError(
            f"distributed bench child failed:\n{proc.stdout[-2000:]}"
            f"\n{proc.stderr[-4000:]}")
    raw = next(ln for ln in proc.stdout.splitlines()
               if ln.startswith(_MARK))
    m = json.loads(raw[len(_MARK):])

    speedup = m["local_s"] / max(m["sharded_s"], 1e-12)
    grid_speedup = m["grid_vmap_s"] / max(m["grid_shard_s"], 1e-12)
    emit("distributed_lmds_sharded", m["sharded_s"],
         f"local_us={m['local_s'] * 1e6:.1f};devices={m['devices']};"
         f"speedup={speedup:.2f}x")
    emit("distributed_grid_config_shard", m["grid_shard_s"],
         f"vmap_us={m['grid_vmap_s'] * 1e6:.1f};k={k};"
         f"speedup={grid_speedup:.2f}x")

    entry = dict(
        benchmark="distributed_shard_map",
        workload=f"lmDS({rows}x{cols}) + grid(k={k})",
        devices=m["devices"],
        local_us_per_call=round(m["local_s"] * 1e6, 1),
        sharded_us_per_call=round(m["sharded_s"] * 1e6, 1),
        speedup=round(speedup, 2),
        grid_vmap_us_per_call=round(m["grid_vmap_s"] * 1e6, 1),
        grid_shard_us_per_call=round(m["grid_shard_s"] * 1e6, 1),
        grid_speedup=round(grid_speedup, 2),
        parity_max_abs_err=max(m["parity_max_abs_err"],
                               m["grid_parity_max_abs_err"]),
        shard_meter=m["shard_meter"],
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
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        _child(*(int(a) for a in sys.argv[i + 1:i + 5]))
    else:
        sys.path.insert(0, "src")
        print("name,us_per_call,derived")
        args = {}
        if "--smoke" in sys.argv:
            args = dict(rows=8192, cols=64, k=8, repeats=2)
        print(json.dumps(main(**args), indent=2))

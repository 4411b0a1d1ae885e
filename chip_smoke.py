#!/usr/bin/env python3
"""Smoke run of the lmDS lifecycle and plan serving on a TPU.

Drives the main path once through the entry points a user calls: the
DSL, the compiler, `LineageRuntime`, the fused segments and the Pallas
gram/xtv/spmm kernels, then scoring behind `ModelServer`. It runs in one
process and starts none. The default run has three phases at the
paper's section 5 scale (`configs/paper_hpo.py`):

  a. dense lmDS over 100,000 x 1,000 for 3 lambdas through one runtime
     with a reuse cache; each beta against a float64 NumPy solve;
  b. the same shape at sparsity 0.1 (bcoo values, block-masked spmm
     kernels) for one lambda, against the same reference;
  c. a 1,000-feature `ModelServer` (max_batch=16) scoring 64 requests
     from 8 client threads with phase a's beta, against solo
     `PreparedScript` calls and NumPy.

`--chips 4` runs only the sharded path and what it is compared with:
lmDS under `use_mesh(data=4)` on 400,000 x 1,000 against the same fit
on one device and NumPy, and `grid_search_lm(mode='shard')` under
`use_mesh(config=4)` against the vmapped grid on one device.

Every phase asserts that no fault-policy action was taken, and that an
executed segment's compiled program holds a `tpu_custom_call` (the
Pallas kernel ran). The default run needs a TPU and exits non-zero
elsewhere; `--rehearse` shrinks the sizes and accepts the CPU:

    python chip_smoke.py                 # one TPU chip
    python chip_smoke.py --chips 4       # a four-chip TPU host
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chip_smoke.py --rehearse --chips 4

Times printed are smoke timings of one cold run, not the benchmark's. The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
REHEARSE_ROWS, REHEARSE_COLS = 6_000, 100
LAMBDAS = (1e-3, 1e-1, 1e1)
GRID_LAMBDAS = tuple(10.0 ** (i / 2 - 3) for i in range(8))
N_REQUESTS, N_CLIENTS = 64, 8
# Normwise relative error of beta (and of predictions) against the
# float64 NumPy reference. The kernels take f32 tiles and accumulate in
# f32 over 1e5 rows; on this well-conditioned Gaussian X (cond(X^T X)
# about 1.5) that leaves errors near 1e-6, while a single bf16 MXU pass
# (unit rounding 2^-9) would leave about 1e-3 and fail.
RTOL_F32 = 1e-4
# Served answers against solo calls of the same script: the same kernel
# on the same row, so only f32 rounding may differ.
RTOL_SERVED = 1e-6
# Policy actions that must stay 0 in a clean run. `stragglers` is a
# latency observation, not an action, and is printed only.
FAULT_ACTIONS = ("injected", "retries", "timeouts", "degradations",
                 "shed", "restarts")


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs only the sharded path on four devices")
    p.add_argument("--rehearse", action="store_true",
                   help="small sizes; accept a CPU backend")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


class CacheEvents:
    """Counts JAX persistent-compile-cache reads that hit and writes."""

    def __init__(self):
        self.hits = self.writes = 0

    def __call__(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


class Smoke:
    def __init__(self, args):
        import jax
        import numpy as np

        from repro.configs.paper_hpo import CONFIG
        self.jax, self.np = jax, np
        self.failures: list[str] = []
        self.on_tpu = jax.devices()[0].platform == "tpu"
        self.rows = REHEARSE_ROWS if args.rehearse else CONFIG.rows
        self.cols = REHEARSE_COLS if args.rehearse else CONFIG.cols
        self.sparsity = CONFIG.sparsity
        self.rng = np.random.default_rng(args.seed)

    # -- checks ---------------------------------------------------------
    def check(self, ok: bool, what: str) -> None:
        log(f"  check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)

    def check_clean(self, phase: str, rt) -> None:
        f = rt.stats.faults
        taken = {k: getattr(f, k) for k in FAULT_ACTIONS}
        log(f"  faults: {taken} stragglers={f.stragglers}")
        self.check(not any(taken.values()) and f.backoff_s == 0.0,
                   f"{phase}: no fault-policy action taken")

    def check_kernels(self, phase: str, keys) -> None:
        """At least one executed program holds the Pallas kernel."""
        from repro.core import get_jit_cache
        entries = get_jit_cache()._entries
        n = sum("tpu_custom_call" in entries[k][0].as_text()
                for k in keys if k in entries)
        log(f"  executed programs: {len(keys)}, with tpu_custom_call: {n}")
        if self.on_tpu:
            self.check(n > 0, f"{phase}: a Pallas kernel ran on the chip")
        else:
            log("  (no TPU: the jnp reference kernels ran; not checked)")

    # -- helpers --------------------------------------------------------
    def gaussian_problem(self, rows: int):
        x = self.rng.standard_normal((rows, self.cols))
        beta = self.rng.standard_normal((self.cols, 1))
        y = x @ beta + 0.1 * self.rng.standard_normal((rows, 1))
        return x, y

    @staticmethod
    def reference(x, y, lams):
        """float64 NumPy betas, one column per lambda."""
        import numpy as np
        g, b = x.T @ x, x.T @ y
        eye = np.eye(x.shape[1])
        return np.concatenate([np.linalg.solve(g + lam * eye, b)
                               for lam in lams], axis=1)

    @staticmethod
    def lmds_expr(X, y, lam):
        from repro.core import ops
        return ops.solve(X.T @ X + lam * ops.eye(X.shape[1]), X.T @ y)

    def show_plan(self, roots, reuse: bool = False, sparse: bool = False):
        from repro.core import compile_plan
        text = compile_plan(roots, reuse_enabled=reuse).explain(
            reuse_active=reuse, sparse=sparse)
        for line in text.splitlines():
            if line.startswith("-- segment"):
                log(f"  plan: {line[3:]}")
        return text

    @contextlib.contextmanager
    def executed(self):
        """Collect the jit-cache keys of every program run in the block."""
        from repro.core import get_jit_cache
        jc = get_jit_cache()
        with jc.pinning() as keys:
            yield keys
        jc.unpin_all(keys)

    def report(self, phase: str, t0: float, rt) -> None:
        st = rt.stats.as_dict()
        st.pop("faults", None)
        log(f"  smoke timing (cold run, not a benchmark): "
            f"{time.perf_counter() - t0:.3f} s, "
            f"{rt.stats.compiles} compiles in "
            f"{rt.stats.spans.s('ds.jit.compile'):.3f} s")
        log(f"  counters: {json.dumps(st, default=str)}")

    # -- phase a ----------------------------------------------------------
    def phase_dense(self):
        from repro.core import LineageRuntime, ReuseCache, input_tensor
        from repro.lifecycle.regression import lmDS
        log(f"phase a: dense lmDS {self.rows}x{self.cols}, "
            f"lambdas {LAMBDAS}")
        t0 = time.perf_counter()
        x, y = self.gaussian_problem(self.rows)
        log(f"  data: {time.perf_counter() - t0:.3f} s")
        rt = LineageRuntime(cache=ReuseCache())
        X, Y = input_tensor("X", x), input_tensor("y", y)
        self.show_plan([self.lmds_expr(X, Y, LAMBDAS[0])], reuse=True)
        t0 = time.perf_counter()
        betas, hits = [], []
        with self.executed() as keys:
            for lam in LAMBDAS:
                betas.append(lmDS(X, Y, reg=lam, runtime=rt))
                hits.append(rt.cache.stats.hits)
        self.report("a", t0, rt)
        ref = self.reference(x, y, LAMBDAS)
        for i, (lam, beta) in enumerate(zip(LAMBDAS, betas)):
            err = rel_err(beta, ref[:, i:i + 1])
            self.check(err <= RTOL_F32,
                       f"a: beta(lambda={lam:g}) rel err {err:.3e} "
                       f"<= {RTOL_F32:g}")
        self.check(hits[-1] > hits[0],
                   f"a: reuse hits after the first lambda "
                   f"({hits[-1] - hits[0]}) > 0")
        self.check_clean("a", rt)
        self.check_kernels("a", keys)
        return betas[0]

    # -- phase b ----------------------------------------------------------
    def phase_sparse(self):
        from repro.core import LineageRuntime, input_tensor
        from repro.lifecycle.regression import lmDS
        lam = LAMBDAS[0]
        log(f"phase b: sparse lmDS {self.rows}x{self.cols} at sparsity "
            f"{self.sparsity}, lambda {lam:g}")
        t0 = time.perf_counter()
        x, y = self.gaussian_problem(self.rows)
        x[self.rng.random(x.shape) >= self.sparsity] = 0.0
        y = x @ self.rng.standard_normal((self.cols, 1)) \
            + 0.1 * self.rng.standard_normal((self.rows, 1))
        log(f"  data: {time.perf_counter() - t0:.3f} s, "
            f"density {self.np.count_nonzero(x) / x.size:.4f}")
        rt = LineageRuntime(sparse_inputs=True)
        X, Y = input_tensor("Xs", x), input_tensor("ys", y)
        text = self.show_plan([self.lmds_expr(X, Y, lam)], sparse=True)
        self.check(":bcoo" in text, "b: X is bound as bcoo")
        t0 = time.perf_counter()
        with self.executed() as keys:
            beta = lmDS(X, Y, reg=lam, runtime=rt)
        self.report("b", t0, rt)
        err = rel_err(beta, self.reference(x, y, [lam]))
        self.check(err <= RTOL_F32,
                   f"b: beta rel err {err:.3e} <= {RTOL_F32:g}")
        self.check_clean("b", rt)
        self.check_kernels("b", keys)

    # -- phase c ----------------------------------------------------------
    def phase_serving(self, beta):
        np = self.np
        from repro.core import LineageRuntime, PreparedScript, input_tensor
        from repro.core import ops
        from repro.serving import ModelServer
        log(f"phase c: ModelServer, {self.cols} features, max_batch=16, "
            f"{N_REQUESTS} requests from {N_CLIENTS} client threads")
        rt = LineageRuntime()
        B = input_tensor("beta", beta)
        # a request is one feature column x; its score is t(x) %*% beta
        script = PreparedScript(lambda x: ops.t(x) @ B, [(self.cols, 1)],
                                runtime=rt)
        reqs = self.rng.standard_normal((N_REQUESTS, self.cols, 1))
        served = [None] * N_REQUESTS
        t0 = time.perf_counter()
        with self.executed() as keys:
            server = ModelServer(script, max_batch=16, runtime=rt).deploy()
            t_deploy = time.perf_counter() - t0
            per = N_REQUESTS // N_CLIENTS

            def client(c):
                for i in range(c * per, (c + 1) * per):
                    served[i] = server.score(reqs[i], timeout=600)[0]

            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(N_CLIENTS)]
            t1 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            t_score = time.perf_counter() - t1
            alive = any(t.is_alive() for t in threads)
            server.shutdown()
            solo = [script(r)[0] for r in reqs]
        log(f"  smoke timing (cold run, not a benchmark): deploy "
            f"{t_deploy:.3f} s, {N_REQUESTS} requests {t_score:.3f} s")
        self.report("c", t0, rt)
        self.check(not alive and all(s is not None for s in served),
                   "c: every request answered")
        if alive:
            return
        served_a, solo_a = np.stack(served), np.stack(solo)
        bitwise = bool(np.array_equal(served_a, solo_a))
        log(f"  served == solo bitwise: {bitwise}")
        err = rel_err(served_a, solo_a)
        self.check(err <= RTOL_SERVED,
                   f"c: served vs solo rel err {err:.3e} <= {RTOL_SERVED:g}")
        ref = np.einsum("rij,ik->rjk", reqs, beta)
        err = rel_err(served_a, ref)
        self.check(err <= RTOL_F32,
                   f"c: served vs NumPy rel err {err:.3e} <= {RTOL_F32:g}")
        self.check(rt.stats.serving.retraces == 0,
                   f"c: serving retraces {rt.stats.serving.retraces} == 0")
        self.check_clean("c", rt)
        self.check_kernels("c", keys)

    # -- four devices -------------------------------------------------------
    def phase_sharded(self):
        jax = self.jax
        from repro.core import LineageRuntime, get_jit_cache, input_tensor
        from repro.distributed import use_mesh
        from repro.lifecycle.regression import lmDS
        from repro.lifecycle.validation import grid_search_lm
        lam = LAMBDAS[0]
        rows = 4 * self.rows
        log(f"phase s: lmDS under use_mesh(data=4) on {rows}x{self.cols}")
        t0 = time.perf_counter()
        x, y = self.gaussian_problem(rows)
        log(f"  data: {time.perf_counter() - t0:.3f} s")
        rt = LineageRuntime()
        X, Y = input_tensor("X4", x), input_tensor("y4", y)
        with use_mesh(data=4):
            text = self.show_plan([self.lmds_expr(X, Y, lam)])
            t0 = time.perf_counter()
            with self.executed() as keys:
                beta_sh = lmDS(X, Y, reg=lam, runtime=rt)
            self.report("s", t0, rt)
        self.check("[sharded]" in text, "s: the plan has [sharded] segments")
        self.check(rt.stats.shard.collectives > 0,
                   f"s: collectives {rt.stats.shard.collectives} > 0")
        # the row-sharded input must really be split over 4 devices
        entries = get_jit_cache()._entries
        split = 0
        for k in keys:
            if "|mesh:" not in k[0] or k not in entries:
                continue
            shardings = entries[k][0].input_shardings[0]
            for s, (shape, *_) in zip(shardings, k[1]):
                if (len(shape) == 2 and len(s.device_set) == 4
                        and 4 * s.shard_shape(shape)[0] == shape[0]):
                    split += 1
        self.check(split > 0,
                   f"s: {split} input(s) row-split over 4 devices")
        for d in jax.devices()[:4]:
            ms = d.memory_stats() or {}
            log(f"  {d}: peak_bytes_in_use "
                f"{ms.get('peak_bytes_in_use', 'not reported')}")
        self.check_clean("s", rt)
        self.check_kernels("s", keys)
        t0 = time.perf_counter()
        rt1 = LineageRuntime()
        beta_1 = lmDS(input_tensor("X1", x), input_tensor("y1", y),
                      reg=lam, runtime=rt1)
        log(f"  one-device fit: smoke timing "
            f"{time.perf_counter() - t0:.3f} s")
        ref = self.reference(x, y, [lam])
        for name, b in (("sharded", beta_sh), ("one-device", beta_1)):
            err = rel_err(b, ref)
            self.check(err <= RTOL_F32,
                       f"s: {name} beta vs NumPy rel err {err:.3e} "
                       f"<= {RTOL_F32:g}")
        err = rel_err(beta_sh, beta_1)
        self.check(err <= RTOL_F32,
                   f"s: sharded vs one-device rel err {err:.3e} "
                   f"<= {RTOL_F32:g}")
        self.check_clean("s (one device)", rt1)
        del x, y

        log(f"phase g: grid_search_lm(mode='shard') under "
            f"use_mesh(config=4), {len(GRID_LAMBDAS)} lambdas, "
            f"{self.rows}x{self.cols}")
        x, y = self.gaussian_problem(self.rows)
        rt_v = LineageRuntime()
        t0 = time.perf_counter()
        betas_v, losses_v = grid_search_lm(
            input_tensor("Xgv", x), input_tensor("ygv", y), GRID_LAMBDAS,
            runtime=rt_v, mode="vmap")
        log(f"  vmapped grid on one device: smoke timing "
            f"{time.perf_counter() - t0:.3f} s")
        rt_c = LineageRuntime()
        t0 = time.perf_counter()
        with use_mesh(config=4), self.executed() as keys:
            betas_c, losses_c = grid_search_lm(
                input_tensor("Xgc", x), input_tensor("ygc", y),
                GRID_LAMBDAS, runtime=rt_c, mode="shard")
        self.report("g", t0, rt_c)
        self.check(rt_c.stats.shard.config_sharded_segments > 0,
                   f"g: config-sharded segments "
                   f"{rt_c.stats.shard.config_sharded_segments} > 0")
        err = rel_err(betas_c, betas_v)
        self.check(err <= RTOL_SERVED,
                   f"g: sharded vs vmapped betas rel err {err:.3e} "
                   f"<= {RTOL_SERVED:g}")
        err = rel_err(losses_c, losses_v)
        self.check(err <= RTOL_SERVED,
                   f"g: sharded vs vmapped losses rel err {err:.3e} "
                   f"<= {RTOL_SERVED:g}")
        err = rel_err(betas_c, self.reference(x, y, GRID_LAMBDAS))
        self.check(err <= RTOL_F32,
                   f"g: sharded betas vs NumPy rel err {err:.3e} "
                   f"<= {RTOL_F32:g}")
        self.check_clean("g", rt_c)
        self.check_kernels("g", keys)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        # scale the streaming budget (256 MB for the paper's 1e8
        # values) with the data, so the small plan streams X in buckets
        # as the paper-size plan does on the chip
        os.environ.setdefault(
            "REPRO_CHUNK_MEM_BUDGET",
            str(REHEARSE_ROWS * REHEARSE_COLS * (256 << 20) // 10**8))
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    if dev.platform != "tpu" and not args.rehearse:
        print("chip_smoke: no TPU found (use --rehearse to run on "
              f"{dev.platform})", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2

    import repro.core  # noqa: F401 — sets x64 and the compile cache
    from repro.core import faults
    events = CacheEvents()
    jax.monitoring.register_event_listener(events)
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")

    smoke = Smoke(args)
    t0 = time.perf_counter()
    with faults.inject(None):
        if args.chips == 4:
            smoke.phase_sharded()
        else:
            beta = smoke.phase_dense()
            smoke.phase_sparse()
            smoke.phase_serving(beta)
    log(f"total smoke timing (not a benchmark): "
        f"{time.perf_counter() - t0:.3f} s")
    log(f"compile cache: {events.hits} hits, {events.writes} writes")
    if smoke.failures:
        log(f"FAILED: {len(smoke.failures)} check(s): {smoke.failures}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

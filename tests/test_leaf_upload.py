"""Leaf-upload reuse: a registry leaf's device copy is kept in the reuse
cache under the leaf's lineage, so a local fused segment that reads a
host-bound leaf gets it copied to the device once per cache instead of
once per call.

Covered: the copy count of a sequential grid over a streamed X (one copy
per leaf, every later lambda a hit) with outputs bitwise equal to a run
without a cache; the reuse counts of intermediates unchanged across
fuse modes at budgets that do and do not hold the copies, and a copy
never evicting an entry; no copy without a cache; the cached copy never
donated at pipeline depth 2; batched and cross-validation grids; a
`ModelServer` request path left as it was.
"""
import numpy as np
import pytest

from repro.core import costmodel
from repro.core.dag import input_tensor
from repro.core.reuse import ReuseCache
from repro.core.runtime import LineageRuntime, PreparedScript
from repro.lifecycle.validation import (cross_validate_lm, grid_search_lm,
                                        make_folds)
from repro.serving import ModelServer

BUDGET = 1 << 16  # 64 KiB: X (256 KiB) streams, as it does at 800 MB
LAMBDAS = [1e-3, 1e-2, 1e-1, 1.0, 10.0]
ENTRY_COUNTS = ("probes", "hits", "misses", "evictions", "bytes_cached")


@pytest.fixture(autouse=True)
def tiny_budget(monkeypatch):
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)


@pytest.fixture
def data(rng):
    Xh = rng.normal(size=(4096, 8))
    yh = Xh @ rng.normal(size=(8, 1)) + 0.1 * rng.normal(size=(4096, 1))
    return Xh, yh, input_tensor("X", Xh), input_tensor("y", yh)


def _grid(X, y, cache, fuse=True, mode="sequential"):
    rt = LineageRuntime(cache=cache, fuse=fuse)
    betas, losses = grid_search_lm(X, y, LAMBDAS, runtime=rt, mode=mode)
    return rt, betas, losses


def _entry_counts(cache):
    return {k: getattr(cache.stats, k) for k in ENTRY_COUNTS}


def test_sequential_grid_copies_each_leaf_once(data):
    Xh, yh, X, y = data
    k = len(LAMBDAS)
    rt, betas, losses = _grid(X, y, ReuseCache())
    st = rt.cache.stats
    # the residual y - X @ beta reads both leaves: one copy of each,
    # then a hit per leaf for every later lambda
    assert st.uploads == 2
    assert st.upload_hits == 2 * (k - 1)
    assert st.upload_bytes == Xh.nbytes + yh.nbytes
    assert st.upload_evictions == 0
    assert rt.stats.spans.n("ds.upload") == 2
    # the stream still slices the host X afresh
    assert rt.stats.streaming.chunks > 0
    assert rt.stats.streaming.chunks_reused == 0
    _, betas0, losses0 = _grid(X, y, None)
    assert np.array_equal(betas, betas0)
    assert losses == losses0
    d = st.as_dict()
    assert (d["uploads"], d["upload_hits"]) == (2, 2 * (k - 1))


def _without_copies(monkeypatch):
    """The runtime as it was before leaf copies: every leaf reaches its
    executable as the host array."""
    monkeypatch.setattr(LineageRuntime, "_upload_keys",
                        lambda self, values, overrides: {})


def _budgets(need, Xh, yh):
    assert need < Xh.nbytes
    return {"none": need,
            # both copies fit when first made; entries put later push
            # them out again
            "squeezed": need + Xh.nbytes + yh.nbytes - 1,
            "ample": 4 << 30}


def _check_copies(st, room, budget):
    if room == "none":
        # beside the entries there is room for y's copy at most, and
        # only until the next entry: X's never fits
        assert st.uploads <= 1 and st.upload_bytes == 0
    elif room == "squeezed":
        assert st.uploads >= 2 and st.upload_evictions >= 1
    else:
        assert (st.uploads, st.upload_evictions) == (2, 0)
    assert st.bytes_cached + st.upload_bytes <= budget


@pytest.mark.parametrize("room", ["none", "squeezed", "ample"])
def test_entry_counts_equal_across_fuse_modes(rng, monkeypatch, room):
    """X in device memory (no stream): the interpreter and the fused
    executor probe, hit, admit and evict the same entries, with the
    copies beside them or not."""
    monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", 1 << 30)
    Xh = rng.normal(size=(2048, 8))
    yh = rng.normal(size=(2048, 1))
    X, y = input_tensor("Xm", Xh), input_tensor("ym", yh)
    rt_i, _, _ = _grid(X, y, ReuseCache(), fuse=False)
    budget = _budgets(rt_i.cache.stats.bytes_cached, Xh, yh)[room]
    rt_i, betas_i, _ = _grid(X, y, ReuseCache(budget), fuse=False)
    rt_f, betas_f, _ = _grid(X, y, ReuseCache(budget), fuse=True)
    assert rt_f.stats.streaming.total == 0
    assert _entry_counts(rt_f.cache) == _entry_counts(rt_i.cache)
    assert rt_i.cache.stats.evictions == 0   # the budget holds them all
    assert rt_i.cache.stats.uploads == 0     # the interpreter copies none
    assert rt_f.cache.stats.hits > 0
    np.testing.assert_allclose(betas_f, betas_i, rtol=1e-10)
    _check_copies(rt_f.cache.stats, room, budget)


@pytest.mark.parametrize("room", ["none", "squeezed", "ample"])
def test_streamed_entry_counts_as_without_copies(data, monkeypatch, room):
    """X streamed: the streaming lane's chunk entries exist only in the
    fused executor, so its counts are held to the same executor without
    copies — and its outputs are bitwise the same."""
    Xh, yh, X, y = data
    rt_f, _, _ = _grid(X, y, ReuseCache())
    budget = _budgets(rt_f.cache.stats.bytes_cached, Xh, yh)[room]
    rt_f, betas_f, losses_f = _grid(X, y, ReuseCache(budget))
    with monkeypatch.context() as m:
        _without_copies(m)
        rt_0, betas_0, losses_0 = _grid(X, y, ReuseCache(budget))
    assert rt_0.cache.stats.uploads == 0
    assert _entry_counts(rt_f.cache) == _entry_counts(rt_0.cache)
    assert rt_f.stats.streaming.as_dict() == rt_0.stats.streaming.as_dict()
    assert np.array_equal(betas_f, betas_0) and losses_f == losses_0
    _check_copies(rt_f.cache.stats, room, budget)


def test_upload_never_evicts_an_entry():
    cache = ReuseCache(budget_bytes=1000)
    cache.put("a", np.zeros(60), cost=1.0, gated=False)          # 480 B
    copies = []

    def copy(h):
        copies.append(h)
        return h.copy()

    big = np.ones(70)                                              # 560 B
    # 480 + 560 > 1000: the leaf is handed back, the entry stays
    assert cache.leaf_upload(("big",), big, copy, 1.0) is big
    assert copies == [] and "a" in cache.entries
    small = np.ones(40)                                            # 320 B
    dev = cache.leaf_upload(("small",), small, copy, 1.0)
    assert dev is not small and len(copies) == 1
    assert cache.leaf_upload(("small",), small, copy, 1.0) is dev
    assert (cache.stats.uploads, cache.stats.upload_hits) == (1, 1)
    # an entry's put takes the copy's room before any entry's
    cache.put("b", np.zeros(40), cost=1e-9, gated=False)           # 320 B
    assert cache.stats.upload_evictions == 1 and cache.stats.evictions == 0
    assert set(cache.entries) == {"a", "b"} and not cache.uploads
    assert cache.stats.upload_bytes == 0
    assert cache.stats.bytes_cached == 800
    cache.leaf_upload(("s2",), np.ones(20), copy, 1.0)             # 160 B
    cache.clear()
    assert not cache.uploads and cache.stats.upload_bytes == 0


def test_no_cache_no_copy(data):
    Xh, yh, X, y = data
    rt, betas, _ = _grid(X, y, None)
    assert rt.stats.spans.n("ds.upload") == 0
    assert "ds.upload" not in rt.stats.as_dict()["spans"]
    assert rt.stats.streaming.chunks > 0
    ref = np.linalg.solve(Xh.T @ Xh + LAMBDAS[0] * np.eye(8), Xh.T @ yh)
    np.testing.assert_allclose(betas[:, :1], ref, rtol=1e-8)


def test_cached_copy_is_never_donated(data, monkeypatch):
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    Xh, yh, X, y = data
    rt, betas, _ = _grid(X, y, ReuseCache())
    assert rt.stats.pipeline.async_segments > 0
    assert rt.cache.stats.upload_hits == 2 * (len(LAMBDAS) - 1)
    copies = {key[0].split(":")[0]: e.value
              for key, e in rt.cache.uploads.items()}
    assert set(copies) == {"X", "y"}
    for name, host in (("X", Xh), ("y", yh)):
        assert not copies[name].is_deleted()
        assert np.array_equal(np.asarray(copies[name]), host)
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "1")
    _, betas1, _ = _grid(X, y, None)
    assert np.array_equal(betas, betas1)


def test_batched_grid_reads_the_copy(data):
    """A vmapped grid passes the leaves as config-invariant operands:
    one copy each, and the same outputs as without a cache."""
    Xh, yh, X, y = data
    rt, betas, losses = _grid(X, y, ReuseCache(), mode="vmap")
    assert rt.stats.batched_segments > 0
    assert rt.cache.stats.uploads == 2
    _, betas0, losses0 = _grid(X, y, None, mode="vmap")
    assert np.array_equal(betas, betas0)
    assert losses == losses0


def test_cross_validation_reuses_fold_copies(rng, monkeypatch):
    Xh = rng.normal(size=(4000, 6))
    yh = Xh @ rng.normal(size=(6, 1))
    fx, fy = make_folds(Xh, yh, 4)
    def cv():
        cache = ReuseCache()
        out = cross_validate_lm(fx, fy, runtime=LineageRuntime(cache=cache),
                                mode="sequential")
        return cache, out

    cache, out = cv()
    with monkeypatch.context() as m:
        _without_copies(m)
        cache0, out0 = cv()
    # each fold's held-out leaves are copied once, then read again by
    # the folds that train on them
    assert cache.stats.uploads >= 2 and cache.stats.upload_hits >= 1
    assert _entry_counts(cache) == _entry_counts(cache0)
    assert np.array_equal(out[0], out0[0]) and out[1] == out0[1]


def test_model_server_copies_nothing(rng):
    Wh = rng.normal(size=(16, 1))
    W = input_tensor("upW", Wh)
    rt = LineageRuntime(cache=ReuseCache())
    script = PreparedScript(lambda x: x @ W, [(1, 16)], runtime=rt)
    xs = [rng.normal(size=(1, 16)) for _ in range(5)]
    with ModelServer(script, runtime=rt, max_batch=4,
                     max_wait_us=200.0) as srv:
        outs = [srv.score(x)[0] for x in xs]
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, x @ Wh, rtol=1e-12)
    st = rt.cache.stats
    assert (st.uploads, st.upload_hits, st.upload_bytes) == (0, 0, 0)
    assert rt.stats.spans.n("ds.upload") == 0

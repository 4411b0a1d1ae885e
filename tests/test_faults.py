"""Fault-tolerant execution (ISSUE 10): deterministic seeded fault
injection (`repro.core.faults`), retry/timeout/backoff, and graceful
degradation across the federated, streaming, and serving paths.

Determinism contract: every injected fault is a pure function of
(kind, call index, seed), so a faulted run is exactly reproducible —
the parity tests assert the degraded result matches the clean run to
1e-12 (bitwise in practice: degradation re-executes the SAME jit-cached
executable) and the recovery counters exactly. `stragglers` is the one
nondeterministic counter (wall-clock through the median+MAD monitor)
and is deliberately excluded from exact assertions.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import costmodel, faults, ops
from repro.core.dag import input_tensor
from repro.core.faults import (
    DeadlineExceededError,
    InjectedFault,
    ServerClosedError,
    SiteFailedError,
    parse_spec,
)
from repro.core.federated import FederatedTensor
from repro.core.reuse import ReuseCache
from repro.core.runtime import LineageRuntime, PreparedScript
from repro.data.csv_io import read_csv_chunks, write_csv
from repro.distributed.fault import StepMonitor
from repro.lifecycle import lmDS_federated
from repro.lifecycle.regression import lmDS
from repro.serving import ModelServer, ScoreFuture

D = 16


def _counters(rt):
    """The deterministic counter tuple (everything but stragglers)."""
    f = rt.stats.faults
    return dict(injected=f.injected, retries=f.retries,
                timeouts=f.timeouts, degradations=f.degradations,
                shed=f.shed, restarts=f.restarts)


def _fed_run(x, y, spec=None, intercept=True, sites=4):
    rt = LineageRuntime()
    fed = FederatedTensor.partition_rows(x, sites)
    with faults.inject(spec) as plan:
        w = lmDS_federated(fed, y, intercept=intercept, runtime=rt)
    return np.asarray(w), rt, plan


@pytest.fixture
def fed_data(rng):
    return rng.normal(size=(200, 6)), rng.normal(size=(200, 1))


# ---------------------------------------------------------------------------
# Spec parsing / plan semantics
# ---------------------------------------------------------------------------

class TestSpecAndPlan:
    def test_parse_spec_round_trip(self):
        plan = parse_spec(
            "seed=42;site_rpc@1,3;site_slow:p=0.1:delay=0.02;"
            "site_dead:site=2")
        assert plan.seed == 42
        kinds = [r.kind for r in plan.rules]
        assert kinds == ["site_rpc", "site_slow", "site_dead"]
        assert plan.rules[0].at == frozenset({1, 3})
        assert plan.rules[1].params["delay"] == pytest.approx(0.02)
        assert plan.rules[2].params["site"] == 2

    def test_parse_spec_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="bogus"):
            parse_spec("bogus@1")

    def test_indexed_firing_is_positional(self):
        plan = parse_spec("seed=7;site_rpc@2")
        hits = [plan.check("site_rpc", site=0) is not None
                for _ in range(4)]
        assert hits == [False, False, True, False]
        assert plan.fired["site_rpc"] == 1
        assert plan.calls["site_rpc"] == 4

    def test_probability_draws_are_seeded(self):
        # same seed -> same firing pattern; different seed -> (almost
        # surely) different pattern at p=0.5 over 64 calls
        def pattern(seed):
            plan = parse_spec(f"seed={seed};chunk_io:p=0.5")
            return [plan.check("chunk_io") is not None
                    for _ in range(64)]
        assert pattern(1) == pattern(1)
        assert pattern(1) != pattern(2)

    def test_inject_stack_overrides_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAULT_SPEC", "site_rpc@0")
        env_plan = faults.active_plan()
        assert env_plan is not None
        with faults.inject(None):            # explicit clean run
            assert faults.active_plan() is None
        with faults.inject("seed=1;chunk_io@0") as p:
            assert faults.active_plan() is p
        assert faults.active_plan() is env_plan

    def test_policy_kill_switch(self, monkeypatch, fed_data):
        monkeypatch.setenv("REPRO_FAULT_POLICY", "off")
        assert not faults.policy_enabled()
        x, y = fed_data
        w0, rt0, _ = _fed_run(x, y)
        # injection entries are no-ops with the policy off
        w1, rt1, plan = _fed_run(x, y, "seed=1;site_rpc@0,1")
        assert not plan.fired
        assert np.abs(w1 - w0).max() == 0.0
        assert rt1.stats.faults.injected == 0


# ---------------------------------------------------------------------------
# Federated: retry, timeout, degradation ladders
# ---------------------------------------------------------------------------

class TestFederatedRecovery:
    def test_transient_rpc_faults_heal_by_retry(self, fed_data):
        x, y = fed_data
        w0, _, _ = _fed_run(x, y)
        w1, rt, plan = _fed_run(x, y, "seed=3;site_rpc@0,1")
        assert np.abs(w1 - w0).max() < 1e-12
        assert _counters(rt) == dict(injected=2, retries=2, timeouts=0,
                                     degradations=0, shed=0, restarts=0)
        assert plan.fired == {"site_rpc": 2}
        assert rt.stats.faults.backoff_s > 0.0

    def test_dead_site_degrades_to_recompute(self, fed_data):
        # 1 dead site of 4 plus 2 transient RPC failures: every fed
        # instruction exhausts retries against site 2, then collects
        # its partition and recomputes locally through the SAME
        # jit-cached executable -> bitwise parity with the clean run
        x, y = fed_data
        w0, _, _ = _fed_run(x, y)
        spec = "seed=11;site_dead:site=2;site_rpc@0,9"
        w1, rt, plan = _fed_run(x, y, spec)
        assert np.abs(w1 - w0).max() < 1e-12
        # 3 fed instructions (fed_map, fed_gram, fed_xtv): dead site
        # burns 3 attempts each (9 injected minus one call where the
        # positional site_rpc rule fired first), transient rules add 2
        assert _counters(rt) == dict(injected=10, retries=7, timeouts=0,
                                     degradations=3, shed=0, restarts=0)
        assert plan.fired == {"site_rpc": 2, "site_dead": 8}

    def test_faulted_run_is_deterministic(self, fed_data):
        x, y = fed_data
        spec = "seed=11;site_dead:site=2;site_rpc@0,9"
        w1, rt1, p1 = _fed_run(x, y, spec)
        w2, rt2, p2 = _fed_run(x, y, spec)
        assert np.abs(w1 - w2).max() == 0.0
        assert _counters(rt1) == _counters(rt2)
        assert dict(p1.fired) == dict(p2.fired)

    def test_slow_site_times_out_then_degrades(self, monkeypatch,
                                               fed_data):
        # every call to site 1 sleeps past the timeout; the attempt-
        # boundary timeout discards the (late) result, retries, then
        # degrades. site_slow never raises -> injected stays 0.
        monkeypatch.setattr(costmodel, "FED_TIMEOUT_S", 0.01)
        x, y = fed_data
        w0, _, _ = _fed_run(x, y, intercept=False)
        w1, rt, plan = _fed_run(
            x, y, "site_slow:p=1:site=1:delay=0.05", intercept=False)
        assert np.abs(w1 - w0).max() < 1e-12
        assert _counters(rt) == dict(injected=0, retries=4, timeouts=6,
                                     degradations=2, shed=0, restarts=0)
        assert plan.fired == {"site_slow": 6}

    def test_lost_data_plane_is_fatal(self, fed_data):
        # site_lost means the partition itself is gone: no degradation
        # rung remains and the failure surfaces with site + instruction
        x, y = fed_data
        with pytest.raises(SiteFailedError) as ei:
            _fed_run(x, y, "seed=1;site_lost:site=1")
        assert ei.value.site == 1
        assert "site 1" in str(ei.value)
        assert ei.value.instruction     # names the fed instruction

    def test_control_plane_surfaces_in_stats(self, fed_data):
        x, y = fed_data
        _, rt, _ = _fed_run(x, y, "seed=11;site_dead:site=2")
        d = rt.stats.as_dict()["faults"]
        assert d["degradations"] == 3
        assert d["incidents"] >= d["injected"] + d["degradations"]
        assert "site_p50_us" in d and "site_p99_us" in d
        # heartbeats: the 3 surviving sites beat on every successful
        # RPC; the dead site never does
        assert d["sites_seen"] == 3
        assert d["dead_sites"] == []    # dead-man switch is 60s

    def test_combined_faults_acceptance(self, rng, tmp_path):
        # the acceptance scenario: site failures + chunk IO errors +
        # one compile failure in ONE seeded run — 1e-12 parity with
        # the clean run, identical counters on every rerun. The jit
        # cache is cleared per run so compile-call indices (and hence
        # the compile@0 firing) are reproducible within one process.
        from repro.core.jit_cache import clear_jit_cache
        xh = rng.normal(size=(208, 7))
        yh = rng.normal(size=(208, 1))
        path = str(tmp_path / "d.csv")
        write_csv(path, np.hstack([xh, yh]))

        def run(spec):
            clear_jit_cache()
            rt = LineageRuntime()
            with faults.inject(spec) as plan:
                parts = [c for _, c in read_csv_chunks(
                    path, 64, chunk_bytes=1 << 12,
                    fault_log=rt.stats.faults)]
                data = np.vstack(parts)
                fed = FederatedTensor.partition_rows(data[:, :-1], 4)
                w = lmDS_federated(fed, data[:, -1:], intercept=True,
                                   runtime=rt)
            return (np.asarray(w), rt,
                    dict(plan.fired) if plan else {})

        spec = ("seed=13;site_dead:site=3;site_rpc@2;"
                "chunk_io@0,1;compile@0")
        w0, _, _ = run(None)
        w1, rt1, fired1 = run(spec)
        w2, rt2, fired2 = run(spec)
        assert np.abs(w1 - w0).max() < 1e-12
        assert np.abs(w1 - w2).max() == 0.0
        assert _counters(rt1) == _counters(rt2) == dict(
            injected=13, retries=10, timeouts=0, degradations=3,
            shed=0, restarts=0)
        assert fired1 == fired2 == {"chunk_io": 2, "compile": 1,
                                    "site_rpc": 1, "site_dead": 9}

    def test_clean_run_has_no_fault_section(self, fed_data):
        x, y = fed_data
        rt = LineageRuntime()
        fed = FederatedTensor.partition_rows(x, 4)
        with faults.inject(None):
            lmDS_federated(fed, y, intercept=True, runtime=rt)
        assert _counters(rt) == dict(injected=0, retries=0, timeouts=0,
                                     degradations=0, shed=0, restarts=0)


# ---------------------------------------------------------------------------
# Compile failures: interpreter fallback
# ---------------------------------------------------------------------------

class TestCompileFallback:
    def test_segment_summary_names_ops(self, rng):
        from repro.core.compiler import compile_plan
        xh = rng.normal(size=(32, 4))
        X = input_tensor("X", xh)
        plan = compile_plan([ops.gram(X)], reuse_enabled=False)
        seg = plan.segments_for(False)[0]
        s = seg.summary()
        assert s.startswith("segment#") and "gram" in s and "ins=" in s

    def test_compile_fault_falls_back_to_interpreter(self, rng):
        # unique shape so the segment is a guaranteed jit-cache miss;
        # faulted run FIRST (the fallback does not populate the cache)
        xh = rng.normal(size=(61, 9))
        yh = rng.normal(size=(61, 1))

        def run(spec):
            rt = LineageRuntime(cache=ReuseCache(), fuse=True)
            with faults.inject(spec):
                w = lmDS(input_tensor("X", xh), input_tensor("y", yh),
                         reg=1e-3, runtime=rt)
            return np.asarray(w), rt

        w1, rt1 = run("seed=1;compile@0")
        w0, _ = run(None)
        assert np.abs(w1 - w0).max() < 1e-12
        assert _counters(rt1) == dict(injected=1, retries=0, timeouts=0,
                                      degradations=1, shed=0, restarts=0)


# ---------------------------------------------------------------------------
# Streaming: chunk IO retry + prefetch-worker death
# ---------------------------------------------------------------------------

BUDGET = 1 << 16


class TestStreamingRecovery:
    def test_csv_read_retries_transient_io(self, rng, tmp_path):
        xh = rng.normal(size=(300, 4))
        path = str(tmp_path / "x.csv")
        write_csv(path, xh)
        flog = faults.FaultLog()
        # two injected IO errors on the first byte-window read, healed
        # by backoff retry (MAX_RETRIES=2 -> third attempt lands)
        with faults.inject("seed=5;chunk_io@0,1"):
            chunks = list(read_csv_chunks(path, 64, chunk_bytes=1 << 12,
                                          fault_log=flog))
        clean = list(read_csv_chunks(path, 64, chunk_bytes=1 << 12))
        assert len(chunks) == len(clean)
        for (o1, c1), (o0, c0) in zip(chunks, clean):
            assert o1 == o0 and (c1 == c0).all()
        assert flog.injected == 2 and flog.retries == 2
        assert flog.backoff_s > 0.0

    def test_csv_read_exhausts_retries(self, rng, tmp_path):
        xh = rng.normal(size=(50, 3))
        path = str(tmp_path / "x.csv")
        write_csv(path, xh)
        with faults.inject("seed=5;chunk_io@0,1,2"):
            with pytest.raises(InjectedFault):
                list(read_csv_chunks(path, 16))

    def test_streamed_lmds_parity_with_io_faults(self, rng, tmp_path,
                                                 monkeypatch):
        # the satellite scenario: streamed lmDS whose ingestion takes 2
        # injected chunk IO errors — byte-identical data after retry,
        # chunked execution, 1e-12 parity with the clean run
        monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
        xh = rng.normal(size=(4096, 8))
        yh = rng.normal(size=(4096, 1))
        path = str(tmp_path / "x.csv")
        write_csv(path, np.hstack([xh, yh]))

        def ingest(spec, flog):
            with faults.inject(spec):
                parts = [c for _, c in read_csv_chunks(
                    path, 512, chunk_bytes=1 << 14, fault_log=flog)]
            return np.vstack(parts)

        flog = faults.FaultLog()
        data1 = ingest("seed=9;chunk_io@0,1", flog)
        data0 = ingest(None, faults.FaultLog())
        assert (data1 == data0).all()
        assert flog.injected == 2 and flog.retries == 2

        def fit(data):
            rt = LineageRuntime(cache=ReuseCache(), fuse=True)
            with faults.inject(None):
                w = lmDS(input_tensor("X", data[:, :-1]),
                         input_tensor("y", data[:, -1:]),
                         reg=1e-3, runtime=rt)
            assert rt.stats.streaming.chunks > 1   # actually streamed
            return np.asarray(w)

        assert np.abs(fit(data1) - fit(data0)).max() < 1e-12

    def test_prefetch_worker_death_degrades_to_sync(self, rng,
                                                    monkeypatch):
        # kill the chunk-prefetch worker mid-stream: the consumer
        # drains in-flight work and finishes the tail synchronously
        # (injection-free), same chunks, bitwise result
        monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
        xh = rng.normal(size=(4096, 8))
        yh = rng.normal(size=(4096, 1))

        def run(spec):
            rt = LineageRuntime(cache=ReuseCache(), fuse=True)
            with faults.inject(spec):
                w = lmDS(input_tensor("X", xh), input_tensor("y", yh),
                         reg=1e-3, runtime=rt)
            return np.asarray(w), rt

        w0, rt0 = run(None)
        w1, rt1 = run("seed=2;chunk_io@1")
        assert np.abs(w1 - w0).max() < 1e-12
        assert rt1.stats.streaming.chunks == rt0.stats.streaming.chunks
        f = rt1.stats.faults
        assert f.injected == 1 and f.degradations == 1


# ---------------------------------------------------------------------------
# Serving: deadlines, supervisor, terminal errors
# ---------------------------------------------------------------------------

def _script(rng, rt):
    W = input_tensor("fltW", rng.normal(size=(D, 1)))
    return PreparedScript(lambda x: (ops.matmul(x, W),), [(1, D)],
                          runtime=rt)


class TestServingFaults:
    def test_deadline_shed_before_dispatch(self, rng):
        rt = LineageRuntime()
        script = _script(rng, rt)
        srv = ModelServer(script, runtime=rt, max_batch=8,
                          adaptive=False, max_wait_us=5e4)
        with faults.inject("seed=1"), srv:
            fut = srv.submit(rng.normal(size=(1, D)), deadline_us=1.0)
            with pytest.raises(DeadlineExceededError):
                fut.result(timeout=5.0)
        assert rt.stats.faults.shed == 1

    def test_supervisor_restarts_coalescer_in_thread(self, rng):
        rt = LineageRuntime()
        script = _script(rng, rt)
        x = rng.normal(size=(1, D))
        before = set(threading.enumerate())
        with faults.inject("seed=1;serving_dispatch@0"):
            with ModelServer(script, runtime=rt, max_batch=8,
                             max_wait_us=500.0) as srv:
                # first dispatch crashes in the pop->dispatch window:
                # exactly that batch fails, the loop restarts in-thread
                with pytest.raises(InjectedFault):
                    srv.score(x, timeout=5.0)
                got, = srv.score(x, timeout=5.0)
        ref, = script(x)
        assert (got == ref).all()
        assert rt.stats.faults.restarts == 1
        assert set(threading.enumerate()) == before

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_dead_dispatcher_surfaces_not_hangs(self, rng):
        # a persistent (non-injected) poison kills the dispatcher once
        # the restart budget is spent; waiters get ServerClosedError
        # instead of hanging, and shutdown delivers terminal errors to
        # anything still queued
        rt = LineageRuntime()
        script = _script(rng, rt)
        srv = ModelServer(script, runtime=rt, max_batch=8,
                          max_wait_us=500.0).deploy()
        srv.max_restarts = 2
        srv._budget_s = None            # poisons every coalesce pass
        fut = srv.submit(rng.normal(size=(1, D)))
        with pytest.raises((ServerClosedError, TypeError)):
            fut.result(timeout=5.0)
        # the thread is dead now; a late submit stays queued until
        # shutdown hands it the terminal error
        deadline = time.monotonic() + 5.0
        while srv._dispatcher_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not srv._dispatcher_alive()
        late = srv.submit(rng.normal(size=(1, D)))
        srv.shutdown()
        with pytest.raises(ServerClosedError):
            late.result(timeout=1.0)

    def test_result_timeout(self):
        fut = ScoreFuture([np.zeros((1, D))])
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            fut.result(timeout=0.1)
        assert time.monotonic() - t0 < 2.0

    def test_score_timeout_kwarg(self, rng):
        rt = LineageRuntime()
        script = _script(rng, rt)
        with ModelServer(script, runtime=rt).deploy() as srv:
            got, = srv.score(rng.normal(size=(1, D)), timeout=5.0)
            assert got.shape == (1, 1)

    def test_dispatch_latencies_metered(self, rng):
        rt = LineageRuntime()
        script = _script(rng, rt)
        with faults.inject(None), \
                ModelServer(script, runtime=rt).deploy() as srv:
            srv.score(rng.normal(size=(1, D)), timeout=5.0)
        d = rt.stats.faults
        assert d.dispatch_monitor.times   # dispatch went through the
        assert "dispatch_p50_us" in d.as_dict()   # rescued monitor


# ---------------------------------------------------------------------------
# Thread hygiene: repeated crash/recover cycles leak nothing
# ---------------------------------------------------------------------------

class TestThreadHygiene:
    def test_serving_crash_cycles_leak_no_threads(self, rng):
        rt = LineageRuntime()
        script = _script(rng, rt)
        x = rng.normal(size=(1, D))
        before = set(threading.enumerate())
        with ModelServer(script, runtime=rt, max_batch=8,
                         max_wait_us=500.0) as srv:
            for i in range(4):
                with faults.inject(f"seed={i};serving_dispatch@0"):
                    with pytest.raises(InjectedFault):
                        srv.score(x, timeout=5.0)
                with faults.inject(None):
                    got, = srv.score(x, timeout=5.0)
                    assert got.shape == (1, 1)
        assert rt.stats.faults.restarts == 4
        assert set(threading.enumerate()) == before

    def test_streaming_crash_cycles_leak_no_threads(self, rng,
                                                    monkeypatch):
        monkeypatch.setattr(costmodel, "CHUNK_MEM_BUDGET", BUDGET)
        monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
        xh = rng.normal(size=(4096, 8))
        yh = rng.normal(size=(4096, 1))
        before = set(threading.enumerate())
        ws = []
        for i in range(3):
            rt = LineageRuntime(cache=ReuseCache(), fuse=True)
            with faults.inject(f"seed={i};chunk_io@1"):
                ws.append(np.asarray(
                    lmDS(input_tensor("X", xh), input_tensor("y", yh),
                         reg=1e-3, runtime=rt)))
            assert rt.stats.faults.degradations == 1
        assert np.abs(ws[0] - ws[1]).max() == 0.0
        assert np.abs(ws[0] - ws[2]).max() == 0.0
        assert set(threading.enumerate()) == before


# ---------------------------------------------------------------------------
# Rescued control plane: StepMonitor bounds
# ---------------------------------------------------------------------------

class TestStepMonitorBounds:
    def test_history_stays_bounded(self):
        m = StepMonitor(max_history=16)
        for i in range(200):
            m.record(i, 0.001)
        assert len(m.times) < 2 * 16
        p50, p99 = m.p50_p99()
        assert p50 == pytest.approx(0.001)

    def test_straggler_still_flagged_after_trim(self):
        m = StepMonitor(max_history=16)
        for i in range(100):
            m.record(i, 1.0)
        assert m.record(100, 10.0)
        assert m.incidents[-1]["step"] == 100

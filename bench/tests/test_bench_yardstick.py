"""The benchmark's yardstick on hand counts: trace reduction, work
formulas, peaks, and the data-driven lookup of metric readers."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import harness
from harness import load_module, tracemod as tr

BENCH = harness.BENCH
Event = tr.Event


def metric(name):
    return harness.load_reader(BENCH, name)


def make_trace():
    """Device 0: an 10-unit loop holding two 2-unit ops, then a gram
    kernel; device 1 busy 5 units; host spans around them."""
    t = tr.Trace()
    t.ops[0] = [Event("%while.1 = (s32[]) while(...)", 0, 10),
                Event("%fusion.2 = f32[8] fusion(...)", 1, 2),
                Event("%fusion.2 = f32[8] fusion(...)", 5, 2),
                Event("%gram_pallas.1 = f32[1024,1024] custom-call(...)",
                      20, 4),
                Event("%all-reduce.3 = f32[4] all-reduce(...)", 30, 2)]
    t.ops[1] = [Event("%fusion.9 = f32[8] fusion(...)", 0, 5)]
    t.host_spans = [Event("bench.trace_start", -1, 0),
                    Event("bench.job", 0, 40),
                    Event("bench.bind", 10, 8),
                    Event("bench.trace_stop", 40, 0)]
    return t


def test_union_and_busy():
    t = make_trace()
    assert tr.union(t.ops[0]) == [(0, 10), (20, 24), (30, 32)]
    # device 0 busy 16 units, device 1 busy 5: mean 10.5 ns
    assert tr.busy_s(t) == pytest.approx(10.5e-9)
    # clipped to [0, 22): device 0 busy 12, device 1 busy 5
    assert tr.busy_s(t, 0, 22) == pytest.approx(8.5e-9)


def test_self_time_counts_nested_ops_once():
    st = tr.self_time_by_name(make_trace().ops[0])
    assert st["while.1"] == pytest.approx(6e-9)
    assert st["fusion.2"] == pytest.approx(4e-9)
    assert st["gram_pallas.1"] == pytest.approx(4e-9)
    assert sum(st.values()) == pytest.approx(16e-9)


def test_matching_and_names():
    t = make_trace()
    assert tr.op_name(t.ops[0][3].name) == "gram_pallas.1"
    assert tr.matching_s(t, r"^gram_pallas\b") == pytest.approx(4e-9)
    assert tr.matching_s(t, metric("gram_roofline").PATTERN) \
        == pytest.approx(4e-9)
    assert tr.matching_s(t, r"^all-reduce") == pytest.approx(2e-9)


def test_window_gaps_and_breakdown():
    t = make_trace()
    win = tr.marked_window(t, "bench.trace_start", "bench.trace_stop")
    assert win == (-1, 40)
    assert tr.idle_gaps(t, *win, dev=0) == [(-1, 0), (10, 20), (24, 30),
                                            (32, 40)]
    bd = tr.breakdown(t, *win)
    assert bd["device_ops"][0] == ["while.1", pytest.approx(6e-9)]
    # ops outside the window are left out
    assert [k for k, _ in tr.breakdown(t, 0, 15)["device_ops"]] \
        == ["while.1", "fusion.9", "fusion.2"]
    # the longest gap (10, 20) lies under bench.bind (10-18) at its middle
    assert bd["idle_gaps"][0] == ["bench.bind", pytest.approx(10e-9)]
    assert bd["idle_gaps"][1] == ["bench.job", pytest.approx(8e-9)]
    assert len(bd["idle_gaps"]) == 4


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with harness.span("bench.trace_start"):
        pass
    jnp.ones(8).sum().block_until_ready()
    with harness.span("bench.trace_stop"):
        pass
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    names = {s.name for s in t.host_spans}
    assert {"bench.trace_start", "bench.trace_stop"} <= names
    assert tr.marked_window(t, "bench.trace_start", "bench.trace_stop")


def job(name):
    return load_module("bench_job_" + name,
                       os.path.join(BENCH, "jobs", name + ".py"))


def test_job_flops_by_hand():
    grid = job("grid_search_lm").flops
    # n=10, d=2, k=3 lambdas: gram 10*2*3=60, xtv 40,
    # per lambda Cholesky 8/3, solves 8, residual 40
    three = {"lambdas": {"count": 3, "log10_min": 0, "log10_max": 1}}
    assert grid(10, 2, three) == pytest.approx(60 + 40 + 3 * (8 / 3 + 8 + 40))
    fit = job("lmDS").flops
    assert fit(10, 2, {"reg": 1.0}) == pytest.approx(60 + 40 + 8 / 3 + 8)
    # the paper's shape: the gram's 1.001e11 dominates one fit
    assert fit(100_000, 1000, {"reg": 1.0}) == pytest.approx(
        1.001e11 + 2e8 + 1e9 / 3 + 2e6)
    assert job("lmDS").gram_shapes(7, 3, {}) == [(7, 3)]


def test_job_lambdas_and_comparison():
    g = job("grid_search_lm")
    lams = g.lambdas({"lambdas": {"count": 3, "log10_min": -1,
                                  "log10_max": 1}})
    assert lams == pytest.approx([0.1, 1.0, 10.0])
    ref = {"betas": np.array([[1.0, 2.0], [0.5, -4.0]]), "losses": [2.0, 4.0]}
    out = {"betas": ref["betas"] * [[1.0, 1.0], [1.0, 1.001]],
           "losses": [2.0, 4.004]}
    # column 2: |-4.004 - -4| / 4; losses: 0.004 / 4
    assert g.compare(out, ref) == pytest.approx(
        {"beta_err": 1e-3, "loss_err": 1e-3})


class FakeTracer:
    def __init__(self):
        self.calls = []

    def start(self, mark=True):
        self.calls.append("start")

    def mark_start(self):
        self.calls.append("mark")

    def stop(self):
        self.calls.append("stop")


def test_probe_slice_follows_the_counter():
    drv = load_module("bench_driver_job", os.path.join(BENCH, "drivers",
                                                       "job.py"))
    count = [0]
    tracer = FakeTracer()
    sl = drv.ProbeSlice(tracer, lambda: count[0], 6, 9).start()
    for n, calls in [(2, []), (3, ["start"]), (6, ["start", "mark"]),
                     (10, ["start", "mark", "stop"])]:
        count[0] = n
        time.sleep(0.02)
        assert tracer.calls == calls, n
    assert sl.finish() is None

    # a slice that never begins is an error the run reports
    sl = drv.ProbeSlice(FakeTracer(), lambda: 4, 6, 9).start()
    assert "never began" in sl.finish()


def test_gram_least_time_by_hand():
    peaks = harness.peaks_for(BENCH, "TPU v5 lite")
    t, bound = metric("gram_roofline").least_s(100_000, 1000, peaks)
    assert bound == "operations"
    assert t == pytest.approx(1e5 * 1000 * 1001 / 197e12)
    t, bound = metric("gram_roofline").least_s(100_000, 8, peaks)
    assert bound == "bytes" and t == pytest.approx(4 * 8e5 / 819e9)


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for(BENCH, "TPU v9 imaginary")


def test_every_metric_and_cell_has_its_file():
    bm = json.load(open(os.path.join(harness.BENCH, "..", "BENCHMARK.json")))
    for m in bm["per_layer"]:
        assert callable(metric(m["name"]).read)
    for w in bm["workloads"]:
        c = harness.load_cell(os.path.dirname(BENCH), w["name"])
        assert callable(harness.load_driver(c))
        assert os.path.exists(os.path.join(BENCH, "jobs",
                                           c.traffic["job"] + ".py"))
        assert os.path.exists(os.path.join(
            BENCH, "data", c.config["data"]["generator"] + ".py"))
        assert c.config["chips"] == w["chips"]


def test_a_new_metric_file_is_found_by_name(tmp_path):
    """Adding a reader file and its entry is all a new metric needs."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.load(open(os.path.join(BENCH, "..", "BENCHMARK.json")))
    bm["per_layer"].append({
        "name": "dummy_jobs", "unit": "jobs", "better": "higher",
        "source": "program_counter", "layer": "whole job (lifecycle)",
        "moves": "job_s", "workloads": ["fit1.dense"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (tmp_path / "bench" / "metrics" / "dummy_jobs.py").write_text(
        "def read(ctx):\n    return float(len(ctx.jobs))\n")
    c = harness.load_cell(str(tmp_path), "fit1.dense")
    names = [m["name"] for m in c.per_layer]
    assert "dummy_jobs" in names and "reuse_hit_share" not in names
    reader = harness.load_reader(c.bench, "dummy_jobs")
    assert reader.read(type("Ctx", (), {"jobs": [1, 2]})) == 2.0
    mod = load_module("copied_trace", str(tmp_path / "bench" / "trace.py"))
    assert mod.op_name("%a.1 = f32[] add(...)") == "a.1"


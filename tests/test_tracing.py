"""The runtime's span table (`repro.core.tracing`): counts and seconds
per `ds.*` name, other threads, the `as_dict` export, the compile
count, op-derived XLA module names, and the spans on the profiler's
clock."""
import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (LineageRuntime, PreparedScript, clear_jit_cache,
                        input_tensor, ops)
from repro.core.tracing import FN_NAME_MAX, SpanLog, segment_fn_name
from repro.lifecycle.regression import lmDS


def test_nested_spans_count_and_time_inclusively():
    log = SpanLog()
    with log("ds.outer"):
        for _ in range(3):
            with log("ds.inner"):
                time.sleep(0.002)
    assert log.n("ds.outer") == 1 and log.n("ds.inner") == 3
    assert log.s("ds.inner") >= 0.006
    # the outer span holds the inner ones
    assert log.s("ds.outer") >= log.s("ds.inner")
    assert log.n("ds.absent") == 0 and log.s("ds.absent") == 0.0


def test_a_span_that_raises_is_still_booked():
    log = SpanLog()
    with pytest.raises(ValueError):
        with log("ds.fails"):
            raise ValueError("boom")
    assert log.n("ds.fails") == 1


def test_spans_from_other_threads_share_the_table():
    log = SpanLog()

    def worker(name):
        for _ in range(2000):
            with log(name):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        # more threads than cores, all on one name: a lost update of the
        # count would show
        threads = [threading.Thread(target=worker, args=("ds.stream.prep",))
                   for _ in range(max(8, 2 * (os.cpu_count() or 1)))]
        for t in threads:
            t.start()
        worker("ds.dispatch")
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert log.n("ds.stream.prep") == 2000 * len(threads)
    assert log.n("ds.dispatch") == 2000


def test_as_dict_exports_the_span_table():
    log = SpanLog()
    with log("ds.wait"):
        pass
    log.add("ds.bind", 0.25)
    log.add("ds.bind", 0.5)
    d = log.as_dict()
    assert list(d) == ["ds.bind", "ds.wait"]
    assert d["ds.bind"] == {"n": 2, "s": 0.75}
    assert d["ds.wait"]["n"] == 1 and d["ds.wait"]["s"] >= 0.0


def _fit(rt, rng, n=64, d=4):
    x = input_tensor("tX", rng.normal(size=(n, d)))
    y = input_tensor("ty", rng.normal(size=(n, 1)))
    return lmDS(x, y, reg=0.5, runtime=rt)


def test_runtime_stats_export_spans_of_a_fit(rng):
    rt = LineageRuntime()
    _fit(rt, rng)
    d = rt.stats.as_dict()
    for name in ("ds.compile_plan", "ds.bind", "ds.dispatch", "ds.wait"):
        assert d["spans"][name]["n"] >= 1, name
    assert all(k.startswith("ds.") for k in d["spans"])
    assert "exec_time_s" not in d and "trace_time_s" not in d
    assert "trace_time_s" not in d["jit_cache"]


def test_compiles_counts_misses_not_warm_replays(rng):
    clear_jit_cache()
    rt = LineageRuntime()
    ps = PreparedScript(lambda a, b: ops.solve(ops.gram(a) + ops.eye(3),
                                               ops.xtv(a, b)),
                        [(20, 3), (20, 1)], runtime=rt)
    ps(rng.normal(size=(20, 3)), rng.normal(size=(20, 1)))
    first = rt.stats.compiles
    assert first == rt.stats.spans.n("ds.jit.compile") > 0
    ps(rng.normal(size=(20, 3)), rng.normal(size=(20, 1)))
    assert rt.stats.compiles == first
    assert rt.stats.jit_cache_hits >= rt.stats.segments - first


def test_segment_fn_name_is_op_derived_and_capped():
    assert segment_fn_name(["combine", "add", "combine", "solve"]) \
        == "ds_combine_add_solve"
    assert segment_fn_name(["fed", "colSums"]) == "ds_fed_colSums"
    assert segment_fn_name(["a.b", "c-d"]) == "ds_a_b_c_d"
    long = segment_fn_name(f"op{i}" for i in range(100))
    assert len(long) <= FN_NAME_MAX and long.startswith("ds_op0_op1_")


def test_xla_modules_carry_the_segment_ops(rng):
    from repro.core.compiler import compile_plan
    from repro.core.segments import build_segment_fn
    x = input_tensor("nX", rng.normal(size=(16, 3)))
    beta = ops.solve(ops.gram(x) + ops.eye(3), ops.xtv(x, x[:, 0:1]))
    plan = compile_plan([beta])
    names = set()
    for seg in plan.segments_for(False):
        fn = build_segment_fn(seg, {})
        assert fn.__name__.startswith("ds_")
        names.add(fn.__name__)
    assert any("solve" in n for n in names)
    solve_seg = next(s for s in plan.segments_for(False)
                     if any(i.node.op == "solve" for i in s.instructions))
    fn = build_segment_fn(solve_seg, {})
    text = jax.jit(fn).lower(
        *[jax.ShapeDtypeStruct(u.shape, np.float64)
          for u in _input_nodes(plan, solve_seg)]).as_text()
    assert f"module @jit_{fn.__name__}" in text


def _input_nodes(plan, seg):
    by_uid = {}
    for ins in plan.instructions:
        by_uid[ins.out_id] = ins.node
        for n in ins.node.inputs:
            by_uid.setdefault(n.uid, n)
    return [by_uid[u] for u in seg.input_uids]


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out


def test_spans_lie_on_the_profilers_clock(rng, tmp_path):
    rt = LineageRuntime()
    _fit(rt, rng)                       # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.call"):
            _fit(rt, rng)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    (call,) = [e for e in events if e[0] == "test.call"]
    for name in ("ds.compile_plan", "ds.bind", "ds.wait"):
        inside = [e for e in events if e[0] == name]
        assert inside, name
        assert all(call[1] <= a and b <= call[2] for _, a, b in inside), name
    # the table counts every span, traced or not
    assert rt.stats.spans.n("ds.compile_plan") == 2

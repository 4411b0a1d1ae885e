"""fit1.dp4 rehearsed at a tiny size on four CPU devices: the result line
at trace 0 and 1, the control, and the fit with the psum of its sharded
gram dropped underneath, which `correct` has to catch. The four runs
share one subprocess with ``--xla_force_host_platform_device_count=4``;
with the one device of the test process the cell does not run."""
import json
import os
import subprocess
import sys

import pytest

import harness

ROOT = os.path.dirname(harness.BENCH)
CELL = "fit1.dp4"

SCRIPT = """
import contextlib, io, json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import harness

def run(*extra, trace=0):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = harness.main(["--workload", "fit1.dp4", "--seed", "3000000041",
                           "--seconds", "0.3", "--trace", str(trace),
                           "--rehearse", *extra], root={root!r})
    lines = buf.getvalue().strip().splitlines()
    return {{"rc": rc, "result": json.loads(lines[-1]) if lines else None}}

out = {{"trace0": run(), "trace1": run(trace=1), "control": run("--control")}}

# the per-shard grams no longer summed over the mesh
from repro.core import backend
from repro.core.jit_cache import clear_jit_cache
backend._KERNEL_BUILDERS["shard_gram"] = lambda attrs: backend._gram
backend._kernel_cached.cache_clear()
clear_jit_cache()
out["no_psum"] = run()
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = SCRIPT.format(bench=harness.BENCH, src=os.path.join(ROOT, "src"),
                         root=ROOT)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=240)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct(runs, trace):
    run = runs[f"trace{trace}"]
    assert run["rc"] == 0
    res = run["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    c = harness.load_cell(ROOT, CELL)
    if trace:
        assert set(res["metrics"]) <= {m["name"] for m in c.per_layer}
        # the span reader finds the placement; the device readers find
        # no TPU plane and the peak-based reader no peaks on the CPU
        assert 0.0 < res["metrics"]["shard_place_share"]["value"] < 100.0
    else:
        assert set(res["metrics"]) == {"job_s", "setup_s"}
    for k, v in res["checks"].items():
        assert v["value"] <= v["limit"], k


@pytest.mark.parametrize("run", ["control", "no_psum"])
def test_not_correct(runs, run):
    assert runs[run]["rc"] == 0
    res = runs[run]["result"]
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


def test_on_one_device_the_cell_does_not_run(capsys):
    rc = harness.main(["--workload", CELL, "--seed", "3000000041",
                       "--seconds", "0.3", "--trace", "0", "--rehearse"],
                      root=ROOT)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_the_cell_lists_its_metrics():
    c = harness.load_cell(ROOT, CELL)
    assert {m["name"] for m in c.e2e} == {"job_s", "setup_s"}
    assert {"shard_place_share", "collective_ms.dp4", "job_mfu.dp4"} <= \
        {m["name"] for m in c.per_layer}


def _trace_ctx(ops, traced="job"):
    tracemod = harness.tracemod
    tr = tracemod.Trace(ops={d: [tracemod.Event(*e) for e in evs]
                             for d, evs in ops.items()})
    return type("Ctx", (), {"traced": traced, "trace": tr,
                            "tracemod": tracemod})


def test_collective_ms_reads_the_busiest_device():
    reader = harness.load_reader(harness.BENCH, "collective_ms.dp4")
    ops = {0: [("%all-reduce-start.3 = f32[1000,1000] all-reduce-start(x)",
                0.0, 1.5e6),
               ("%all-reduce-done.3 = f32[1000,1000] all-reduce-done(y)",
                2e6, 0.5e6),
               ("%fusion.7 = f32[1000,1000] fusion(z)", 3e6, 9e6)],
           1: [("%all-reduce.1 = f32[1000,1] all-reduce(v)", 0.0, 3e6)]}
    assert reader.read(_trace_ctx(ops)) == pytest.approx(3.0)
    assert reader.read(_trace_ctx(ops, traced="probes 1-2")) is None
    assert reader.read(_trace_ctx({0: ops[0][2:]})) is None


def test_span_and_mfu_readers_on_older_counters():
    """A program without `ds.shard.place` gives no `shard_place_share`;
    `job_mfu.dp4` is `job_mfu` itself."""
    place = harness.load_reader(harness.BENCH, "shard_place_share")
    jobs = [{"t0": 0.0, "t1": 2.0, "counters": {"spans": {
        "ds.wait": {"n": 1, "s": 0.5}}}}]
    ctx = type("Ctx", (), {"jobs": jobs})
    assert place.read(ctx) is None
    jobs[0]["counters"]["spans"]["ds.shard.place"] = {"n": 2, "s": 1.5}
    assert place.read(ctx) == pytest.approx(75.0)
    drv = type("Drv", (), {"rows": 2_400_000, "cols": 1000,
                           "job_mod": harness.load_module(
                               "bench_job_lmDS_mesh", os.path.join(
                                   harness.BENCH, "jobs", "lmDS_mesh.py"))})
    ctx = type("Ctx", (), {"peaks": {"bf16_flops_per_s": 197e12},
                           "jobs": jobs, "jobs_s": 3.5, "chips": 4,
                           "driver": drv, "traffic": {}})
    mfu = harness.load_reader(harness.BENCH, "job_mfu")
    assert harness.load_reader(harness.BENCH, "job_mfu.dp4").read(ctx) == \
        mfu.read(ctx) > 0.0

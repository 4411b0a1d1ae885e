"""The per-layer metrics read from the program's own spans, rehearsed:
each share is printed in a traced run of each job cell, lies in
[0, 100], and the shares, which cover disjoint host work, sum to at
most 100."""
import json
import os

import pytest

import harness

ROOT = os.path.dirname(harness.BENCH)
SHARES = ("plan_compile_share", "stream_host_share", "device_wait_share")


def traced_rehearsal(capsys, cell: str) -> dict:
    rc = harness.main(["--workload", cell, "--seed", "3000000037",
                       "--seconds", "0.3", "--trace", "1", "--rehearse"],
                      root=ROOT)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["hpo70.dense", "fit1.dense"])
def test_span_shares_are_printed_and_add_up(capsys, cell):
    res = traced_rehearsal(capsys, cell)
    assert res["correct"] is True
    m = res["metrics"]
    for name in SHARES:
        assert name in m, name
        assert m[name]["unit"] == "%"
        assert 0.0 <= m[name]["value"] <= 100.0, (name, m[name])
    assert sum(m[name]["value"] for name in SHARES) <= 100.0
    # the solve's device time needs a chip's trace: a CPU rehearsal
    # has no device plane to read it from
    assert "solve_device_ms" not in m


def test_readers_find_nothing_without_spans():
    """A program without the span table (an older checkout) leaves the
    metrics out instead of failing the run."""
    ctx = type("Ctx", (), {"jobs": [{"t0": 0.0, "t1": 1.0,
                                     "counters": {"executed": 3}}],
                           "traced": "job"})
    ctx.trace = harness.tracemod.Trace()
    for name in SHARES + ("solve_device_ms",):
        assert harness.load_reader(harness.BENCH, name).read(ctx) is None


def test_shares_by_hand():
    spans = {"ds.compile_plan": {"n": 1, "s": 0.1},
             "ds.stream.dispatch": {"n": 13, "s": 0.2},
             "ds.stream.fingerprint": {"n": 13, "s": 0.05},
             "ds.stream.prep": {"n": 13, "s": 0.3},
             "ds.wait": {"n": 14, "s": 0.25}}
    ctx = type("Ctx", (), {"jobs": [
        {"t0": 0.0, "t1": 1.0, "counters": {"spans": spans}},
        {"t0": 2.0, "t1": 3.0, "counters": {"spans": spans}}]})
    read = {n: harness.load_reader(harness.BENCH, n).read(ctx)
            for n in SHARES}
    assert read["plan_compile_share"] == pytest.approx(10.0)
    # the worker's overlapped prep is left out
    assert read["stream_host_share"] == pytest.approx(25.0)
    assert read["device_wait_share"] == pytest.approx(25.0)


def test_solve_device_ms_by_hand():
    Event = harness.tracemod.Event
    tr = harness.tracemod.Trace()
    tr.modules[0] = [Event("jit_ds_combine_add_solve(7)", 0, 4e6),
                     Event("jit_ds_chunk_gram_chunk_xtv(3)", 5e6, 1e6),
                     Event("jit_ds_combine_add_solve(7)", 9e6, 2e6)]
    ctx = type("Ctx", (), {"traced": "job", "trace": tr})
    reader = harness.load_reader(harness.BENCH, "solve_device_ms")
    assert reader.read(ctx) == pytest.approx(3.0)
    ctx.traced = "probes 130-134"       # a slice holds part of a job
    assert reader.read(ctx) is None

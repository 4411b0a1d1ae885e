"""stream_copy_gbps: the streaming lane's copy rate, read from the window
jobs' `ds.stream.copy` span and their streamed bytes; left out where the
program has no such span."""
import json
import os

import pytest

import harness

ROOT = os.path.dirname(harness.BENCH)


def reader():
    return harness.load_reader(harness.BENCH, "stream_copy_gbps")


def ctx_of(jobs):
    return type("Ctx", (), {"jobs": jobs})


def test_none_without_the_copy_span():
    """An older program streams X without the worker's copy span (or a
    job streams nothing): the metric is left out."""
    old = {"spans": {"ds.stream.dispatch": {"n": 13, "s": 0.01},
                     "ds.stream.prep": {"n": 13, "s": 0.001},
                     "ds.wait": {"n": 15, "s": 0.6}},
           "streaming": {"chunks": 13, "bytes_streamed": 800_800_000}}
    assert reader().read(ctx_of([{"counters": old},
                                 {"counters": None}])) is None
    assert reader().read(ctx_of([{"counters": {"executed": 3}}])) is None
    assert reader().read(ctx_of([])) is None


def test_rate_from_a_dumped_span_table():
    def job(copy_s, streamed):
        return {"t0": 0.0, "t1": 1.0, "counters": {
            "spans": {"ds.stream.copy": {"n": 13, "s": copy_s},
                      "ds.wait": {"n": 2, "s": 0.3}},
            "streaming": {"chunks": 13, "bytes_streamed": streamed}}}

    jobs = [job(0.1, 800_800_000), job(0.12, 800_800_000),
            {"t0": 1.0, "t1": 2.0, "counters": {"spans": {}}}]
    got = reader().read(ctx_of(jobs))
    assert got == pytest.approx(2 * 800_800_000 / 0.22 / 1e9)


def test_printed_in_a_traced_rehearsal(capsys, monkeypatch):
    """The rehearsal's tiny X would not pay for the prefetch worker in
    auto mode; at depth 2 the dense lane copies its buckets there."""
    monkeypatch.setenv("REPRO_PIPELINE_DEPTH", "2")
    rc = harness.main(["--workload", "fit1.dense", "--seed", "3000000041",
                       "--seconds", "0.3", "--trace", "1", "--rehearse"],
                      root=ROOT)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    m = res["metrics"]["stream_copy_gbps"]
    assert m["unit"] == "GB/s"
    assert m["value"] > 0.0

"""upload_hit_share, rehearsed: a traced hpo70.dense run prints the share
of leaf-upload requests served by a resident device copy, fit1.dense
(no reuse cache) does not, and the reader leaves the metric out on an
older program's counters."""
import json
import os

import pytest

import harness

ROOT = os.path.dirname(harness.BENCH)


def traced_rehearsal(capsys, cell: str) -> dict:
    rc = harness.main(["--workload", cell, "--seed", "3000000037",
                       "--seconds", "0.3", "--trace", "1", "--rehearse"],
                      root=ROOT)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_upload_hit_share_in_the_grid(capsys, monkeypatch):
    """At the paper's size `parfor` runs the 70 lambdas one by one (the
    rehearsal's tiny X would batch them): X and y are copied to the
    device once each and read from the copies by the other 69."""
    import repro.lifecycle.validation as validation
    monkeypatch.setattr(validation, "choose_mode", lambda *a: "sequential")
    res = traced_rehearsal(capsys, "hpo70.dense")
    assert res["correct"] is True
    m = res["metrics"]
    assert m["upload_hit_share"]["unit"] == "%"
    assert 0.0 < m["upload_hit_share"]["value"] <= 100.0
    assert m["upload_hit_share"]["value"] == pytest.approx(100.0 * 69 / 70)
    assert m["reuse_hit_share"]["value"] > 0.0


def test_upload_hit_share_not_in_fit1(capsys):
    res = traced_rehearsal(capsys, "fit1.dense")
    assert res["correct"] is True
    assert "upload_hit_share" not in res["metrics"]


def test_upload_hit_share_without_the_counter():
    """An older program's reuse counters have no upload counts: the
    metric is left out."""
    reader = harness.load_reader(harness.BENCH, "upload_hit_share")
    old = {"probes": 10, "hits": 4, "misses": 6, "evictions": 0,
           "bytes": 8, "time_saved_s": 0.1}
    ctx = type("Ctx", (), {"jobs": [{"t0": 0.0, "t1": 1.0,
                                     "counters": {"reuse": old}},
                                    {"t0": 1.0, "t1": 2.0,
                                     "counters": None}]})
    assert reader.read(ctx) is None
    new = dict(old, uploads=2, upload_hits=138, upload_bytes=8,
               upload_evictions=0)
    ctx.jobs = [{"counters": {"reuse": new}}, {"counters": {"reuse": new}}]
    assert reader.read(ctx) == pytest.approx(100.0 * 138 / 140)

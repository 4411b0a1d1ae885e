"""Each cell rehearsed at a tiny size on the CPU, its control, and the
timed path broken underneath: `correct` has to come out false."""
import json
import os
import shutil

import numpy as np
import pytest

import harness

JOB_CELLS = ["hpo70.dense", "fit1.dense"]
ROOT = os.path.dirname(harness.BENCH)


@pytest.fixture
def root():
    return ROOT


@pytest.fixture
def run(capsys, root):
    def once(cell, *extra, trace=0, seconds=0.3):
        rc = harness.main(["--workload", cell, "--seed", "3000000019",
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--rehearse", *extra], root=root)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])
    return once


def test_without_a_chip_the_run_fails(capsys):
    rc = harness.main(["--workload", "hpo70.dense", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", JOB_CELLS)
def test_rehearsal_prints_the_result_line(run, root, cell, trace):
    res = run(cell, trace=trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["rehearsal"].startswith("CPU rehearsal")
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    c = harness.load_cell(root, cell)
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "setup_compile_s" in res["metrics"]
        assert set(res["metrics"]) <= {m["name"] for m in c.per_layer}
    else:
        assert set(res["metrics"]) == {m["name"] for m in c.e2e}
    for k, v in res["checks"].items():
        assert v["value"] <= v["limit"], k


@pytest.mark.parametrize("cell", JOB_CELLS)
def test_the_control_is_not_correct(run, cell):
    res = run(cell, "--control")
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


def _arrays(t):
    from repro.core.dag import LEAVES
    return np.asarray(LEAVES.values[t.node.uid])


def _job_fault(monkeypatch, fault):
    """Break the lifecycle entries the job cells call."""
    from repro.core import input_tensor
    from repro.lifecycle import regression, validation
    grid, fit = validation.grid_search_lm, regression.lmDS

    def bad_grid(X, y, lams, runtime=None, mode="auto"):
        if fault == "unchanged":        # the initial state, never stepped
            yy = _arrays(y)
            return (np.zeros((X.shape[1], len(lams))),
                    [float(np.sum(yy ** 2))] * len(lams))
        if fault == "half_batch":       # every other lambda, duplicated
            b, ls = grid(X, y, lams[::2], runtime=runtime, mode=mode)
            b = np.repeat(b, 2, axis=1)[:, :len(lams)]
            return b, list(np.repeat(ls, 2)[:len(lams)])
        b, ls = grid(X, y, lams, runtime=runtime, mode=mode)
        b = b.copy()
        b[0, 0] *= 1 + 1e-4             # one answer altered
        return b, ls

    def bad_fit(X, y, reg=1e-7, intercept=False, runtime=None):
        if fault == "unchanged":
            return np.zeros((X.shape[1], 1))
        if fault == "half_batch":       # the first half of the rows only
            xx, yy = _arrays(X), _arrays(y)
            h = len(xx) // 2
            return fit(input_tensor("Xh", xx[:h]), input_tensor("yh", yy[:h]),
                       reg=reg, runtime=runtime)
        b = fit(X, y, reg=reg, intercept=intercept, runtime=runtime).copy()
        b[0, 0] *= 1 + 1e-4
        return b

    monkeypatch.setattr(validation, "grid_search_lm", bad_grid)
    monkeypatch.setattr(regression, "lmDS", bad_fit)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("cell", JOB_CELLS)
def test_a_broken_job_is_not_correct(run, monkeypatch, cell, fault):
    _job_fault(monkeypatch, fault)
    assert run(cell)["correct"] is False


def test_a_new_traffic_mix_is_data_alone(tmp_path, capsys):
    """A cell over a new traffic file runs without an edit to any code."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bm["workloads"].append({"name": "hpo5.dense", "config": "lm_dense_100k",
                            "traffic": "hpo5", "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        m.get("workloads", []).append("hpo5.dense")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    traffic = json.load(open(tmp_path / "bench" / "traffic" / "hpo70.json"))
    traffic["lambdas"] = {"count": 5, "log10_min": -2.0, "log10_max": 2.0}
    (tmp_path / "bench" / "traffic" / "hpo5.json").write_text(
        json.dumps(traffic))
    rc = harness.main(["--workload", "hpo5.dense", "--seed", "2147490001",
                       "--seconds", "0.3", "--trace", "0", "--rehearse"],
                      root=str(tmp_path))
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    assert {"job_s", "setup_s"} <= set(res["metrics"])
    assert set(res["checks"]) == {"beta_err", "loss_err"}

"""Placement of a sharded segment's host operands on the mesh.

On a forced 4-device host mesh, `lmDS` under `use_mesh(data=4)` runs
four segments: `[shard_gram, literal]` (reads X), `[eye, mul, add]`,
`[shard_xtv]` (reads X and y) and `[solve]`. The runtime places each
host operand of a sharded segment on the mesh once a run, inside the
span `ds.shard.place`, and a later sharded segment that reads the same
value gets the placed copy; a device's part over
`costmodel.PLACE_PIECE_BYTES` crosses in row pieces. The fit is held
to a float64 NumPy reference written from the definition, and to the
same fit on one device.

The mesh cases run in one subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
`tests/test_sharded.py` pattern) that prints its readings as JSON.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, COLS, REG = 4096, 64, 1e-3

# Both fits are float64 end to end on the CPU (the Pallas kernels are a
# TPU path); the sharded gram and xtv sum four per-shard partials where
# one device sums all rows at once, so the betas differ by the rounding
# of a reordered float64 sum: a few ulps, scaled by the condition number
# of X^T X + lambda I (about 1.5 for 4,096 x 64 Gaussian rows).
TOL_REFERENCE = 1e-12
TOL_ONE_DEVICE = 1e-12

SCRIPT = f"""
import json
import numpy as np
from repro.core import LineageRuntime, input_tensor, ops
from repro.core.compiler import compile_plan
from repro.distributed import use_mesh
from repro.lifecycle.regression import lmDS

rng = np.random.default_rng(20261018)
X = rng.standard_normal(({ROWS}, {COLS}))
y = (X @ rng.standard_normal(({COLS}, 1))
     + 0.1 * rng.standard_normal(({ROWS}, 1)))
x, yy = input_tensor("X", X), input_tensor("y", y)
out = {{"x_bytes": X.nbytes, "y_bytes": y.nbytes}}

with use_mesh(data=4):
    rt = LineageRuntime()
    beta = lmDS(x, yy, reg={REG}, runtime=rt)
out["beta"] = np.asarray(beta).ravel().tolist()
out["shard"] = rt.stats.shard.as_dict()
out["place"] = rt.stats.spans.as_dict().get("ds.shard.place")
out["one_device"] = np.asarray(
    lmDS(x, yy, reg={REG}, runtime=LineageRuntime())).ravel().tolist()

# an operand that is already a device array (here the output of a local
# segment, y * 2, replicated on the mesh) is not placed: only X is. The
# xtv executable cached by the fit above was compiled for y split by
# rows, and the device array is moved to that layout on the device
with use_mesh(data=4):
    rt2 = LineageRuntime()
    rt2.evaluate([ops.solve(x.T @ x + {REG} * ops.eye({COLS}),
                            x.T @ (yy * 2.0))])
out["device_operand"] = rt2.stats.shard.as_dict()

# a placed copy lives for one run: a second run of the same plan places
# X and y anew (each benchmark job binds X afresh) and gives the same fit
with use_mesh(data=4):
    plan = compile_plan([ops.solve(x.T @ x + {REG} * ops.eye({COLS}),
                                   x.T @ yy)])
    rt3 = LineageRuntime()
    first = rt3.run_plan(plan)[0]
    after_first = rt3.stats.shard.as_dict()
    again = rt3.run_plan(plan)[0]
out["rerun"] = {{"after_first": after_first,
                 "after_second": rt3.stats.shard.as_dict(),
                 "equal": bool(np.array_equal(first, again))}}
# each device's part of X copied in row pieces of at most 100,000 bytes
# and joined on its device: the same fit, bitwise, and the same counts
from repro.core import costmodel
costmodel.PLACE_PIECE_BYTES = 100_000
with use_mesh(data=4):
    rt4 = LineageRuntime()
    pieced = lmDS(x, yy, reg={REG}, runtime=rt4)
out["pieces"] = {{"equal": bool(np.array_equal(pieced, beta)),
                  "shard": rt4.stats.shard.as_dict()}}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=120)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def reference_beta(reg: float) -> np.ndarray:
    """beta = solve(X^T X + lambda I, X^T y) in float64 NumPy, on the
    subprocess's data."""
    rng = np.random.default_rng(20261018)
    X = rng.standard_normal((ROWS, COLS))
    y = X @ rng.standard_normal((COLS, 1)) \
        + 0.1 * rng.standard_normal((ROWS, 1))
    return np.linalg.solve(X.T @ X + reg * np.eye(COLS), X.T @ y).ravel()


def _rel(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _reference(r):
    assert _rel(r["beta"], reference_beta(REG)) <= TOL_REFERENCE


def _one_device(r):
    assert _rel(r["beta"], r["one_device"]) <= TOL_ONE_DEVICE


def _placed_once(r):
    # X is read by two sharded segments and crosses to the mesh once
    assert r["place"]["n"] == 2
    assert r["shard"]["placements"] == 2
    assert r["shard"]["placed_bytes"] == r["x_bytes"] + r["y_bytes"]


def _collectives(r):
    assert r["shard"]["sharded_segments"] == 2
    assert r["shard"]["collectives"] == 2
    assert r["shard"]["reshards"] == 0


def _device_operand_not_placed(r):
    assert r["device_operand"]["collectives"] == 2
    assert r["device_operand"]["placements"] == 1
    assert r["device_operand"]["placed_bytes"] == r["x_bytes"]


def _placed_once_a_run(r):
    rr = r["rerun"]
    assert rr["after_first"]["placements"] == 2
    assert rr["after_second"]["placements"] == 4
    assert rr["after_second"]["placed_bytes"] == \
        2 * rr["after_first"]["placed_bytes"]
    assert rr["equal"]


def _placed_in_pieces(r):
    assert r["pieces"]["equal"]
    assert r["pieces"]["shard"] == r["shard"]


CASES = {
    "matches_the_float64_reference": _reference,
    "matches_one_device": _one_device,
    "x_and_y_placed_once": _placed_once,
    "two_collectives_no_reshard": _collectives,
    "device_operand_not_placed": _device_operand_not_placed,
    "placed_once_a_run": _placed_once_a_run,
    "placed_in_pieces": _placed_in_pieces,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_lane(mesh_run, case):
    CASES[case](mesh_run)

"""Job: one closed-form ridge fit, `lmDS(X, y, reg)`, compiled under the
device mesh the traffic names (`mesh: {"data": n}`), so X is row-sharded
over the mesh's data axis.

Traffic keys: `reg` (lambda), `mesh`.

Reference, control, comparison and work formulas are those of
`jobs/lmDS.py`. A sharded fit runs at least one sharded segment and two
collectives (the psums of X^T X and X^T y); the runtime's shard meter
counts both only when the mesh is real, so a job that shows fewer raises
and counts as failed, and a local fallback cannot pass for this job.
"""
from __future__ import annotations

import os

import harness

_lmDS = harness.load_module(
    "bench_job_lmDS",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "lmDS.py"))
expected, control, compare = _lmDS.expected, _lmDS.control, _lmDS.compare
flops, gram_shapes = _lmDS.flops, _lmDS.gram_shapes


def run(leaves: dict, traffic: dict, runtime) -> dict:
    from repro.distributed import use_mesh
    with use_mesh(data=traffic["mesh"]["data"]):
        out = _lmDS.run(leaves, traffic, runtime)
    shard = runtime.stats.shard
    if shard.sharded_segments < 1 or shard.collectives < 2:
        raise RuntimeError("the fit did not run sharded on the mesh: "
                           f"{shard.as_dict()}")
    return out

"""Job: one closed-form ridge fit, `lmDS(X, y, reg)`.

Traffic key: `reg` (lambda).

Required operations of one job over X (n x d) and y (n x 1): the gram
X^T X n*d*(d+1) (the symmetric half, 2 operations a product), xtv
2*n*d, a Cholesky d^3/3 and two triangular solves 2*d^2.
"""
from __future__ import annotations

import numpy as np

from harness import reference


def run(leaves: dict, traffic: dict, runtime) -> dict:
    from repro.lifecycle.regression import lmDS
    return {"betas": np.asarray(lmDS(leaves["X"], leaves["y"],
                                     reg=traffic["reg"], runtime=runtime))}


def expected(data: dict, traffic: dict) -> dict:
    betas, _ = reference.fit(data["X"], data["y"], [traffic["reg"]],
                             with_loss=False)
    return {"betas": betas}


def control(data: dict, traffic: dict) -> dict:
    betas, _ = reference.control_fit(data["X"], data["y"], [traffic["reg"]],
                                     with_loss=False)
    return {"betas": betas}


def compare(out: dict, ref: dict) -> dict[str, float]:
    return {"beta_err": reference.beta_err(out["betas"], ref["betas"])}


def flops(n: int, d: int, traffic: dict) -> float:
    return n * d * (d + 1) + 2 * n * d + d ** 3 / 3 + 2 * d ** 2


def gram_shapes(n: int, d: int, traffic: dict) -> list[tuple[int, int]]:
    return [(n, d)]

"""Job: `grid_search_lm(X, y, lambdas, mode)`, one lmDS model per lambda
with its training loss (the paper's hyper-parameter search, Fig. 5).

Traffic keys: `lambdas` ({count, log10_min, log10_max}, log-spaced) and
`mode` (the parfor mode, `auto` by default).

Required operations of one job over X (n x d), y (n x 1) and k lambdas:
the gram X^T X n*d*(d+1) (the symmetric half, 2 operations a product),
xtv 2*n*d, and per lambda a Cholesky d^3/3, two triangular solves 2*d^2
and the residual X beta 2*n*d. Work the program repeats or pads does
not count.
"""
from __future__ import annotations

import numpy as np

from harness import reference


def lambdas(traffic: dict) -> list[float]:
    s = traffic["lambdas"]
    return [float(v) for v in np.logspace(s["log10_min"], s["log10_max"],
                                          s["count"])]


def run(leaves: dict, traffic: dict, runtime) -> dict:
    from repro.lifecycle.validation import grid_search_lm
    betas, losses = grid_search_lm(leaves["X"], leaves["y"], lambdas(traffic),
                                   runtime=runtime,
                                   mode=traffic.get("mode", "auto"))
    return {"betas": np.asarray(betas), "losses": losses}


def expected(data: dict, traffic: dict) -> dict:
    betas, losses = reference.fit(data["X"], data["y"], lambdas(traffic),
                                  with_loss=True)
    return {"betas": betas, "losses": losses}


def control(data: dict, traffic: dict) -> dict:
    betas, losses = reference.control_fit(data["X"], data["y"],
                                          lambdas(traffic), with_loss=True)
    return {"betas": betas, "losses": losses}


def compare(out: dict, ref: dict) -> dict[str, float]:
    return {"beta_err": reference.beta_err(out["betas"], ref["betas"]),
            "loss_err": reference.loss_err(out["losses"], ref["losses"])}


def flops(n: int, d: int, traffic: dict) -> float:
    per_lambda = d ** 3 / 3 + 2 * d ** 2 + 2 * n * d
    return n * d * (d + 1) + 2 * n * d + traffic["lambdas"]["count"] * per_lambda


def gram_shapes(n: int, d: int, traffic: dict) -> list[tuple[int, int]]:
    """The grams X^T X one job needs: one, reused across the lambdas."""
    return [(n, d)]

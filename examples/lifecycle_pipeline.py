"""End-to-end data science lifecycle (the paper's core scenario):

  raw CSV  ->  generated reader  ->  schema detection  ->  cleaning
  (outliers + imputation)  ->  transformencode  ->  feature selection
  (steplm)  ->  hyper-parameter search + cross-validation with
  lineage-based reuse  ->  model checkpoint with lineage manifest.

    PYTHONPATH=src python examples/lifecycle_pipeline.py
"""
import os
import sys
import tempfile

sys.path.insert(0, "src")

import numpy as np

from repro.checkpoint import store
from repro.core import LineageRuntime, ReuseCache, input_tensor
from repro.core.hetero import DataTensor, transformencode
from repro.data.csv_io import make_reader
from repro.lifecycle import (cross_validate_lm, grid_search_lm,
                             impute_by_mean, outlier_by_iqr, steplm)
from repro.lifecycle.validation import make_folds


def synthesize_messy_csv(path: str, n: int = 4000) -> np.ndarray:
    """A raw file with categoricals, outliers and missing values."""
    rng = np.random.default_rng(42)
    age = rng.integers(18, 80, n).astype(float)
    income = rng.lognormal(10, 0.5, n)
    income[rng.random(n) < 0.02] *= 50          # gross outliers
    tenure = rng.exponential(5, n)
    region = rng.choice(["north", "south", "east", "west"], n)
    score = (0.04 * age + 0.8 * np.log(income) - 0.2 * tenure
             + (region == "north") * 1.5 + rng.normal(0, 0.3, n))
    rows = []
    for i in range(n):
        inc = "" if rng.random() < 0.05 else f"{income[i]:.2f}"  # missing
        rows.append(f"{age[i]:.0f},{inc},{tenure[i]:.3f},{region[i]},"
                    f"{score[i]:.4f}")
    with open(path, "w") as f:
        f.write("\n".join(rows) + "\n")
    return score


def main():
    tmp = tempfile.mkdtemp()
    csv_path = os.path.join(tmp, "customers.csv")
    synthesize_messy_csv(csv_path)
    print(f"raw file: {csv_path} ({os.path.getsize(csv_path)} bytes)")

    # -- ingestion via a reader GENERATED from a format descriptor (§4.2)
    reader = make_reader({"delimiter": ",", "columns": [
        ("age", "f64"), ("income", "f64"), ("tenure", "f64"),
        ("region", "str"), ("score", "f64")]})
    cols = reader(csv_path)
    dt = DataTensor.from_dict(
        {k: cols[k] for k in ("age", "income", "tenure", "region")},
        types={"region": "str"})
    print("detected schema:", dt.schema)

    # -- cleaning: winsorize outliers, impute missing (mask algebra, §4.2)
    x_num = dt.numeric_matrix()
    x_num = outlier_by_iqr(input_tensor("Xraw", x_num), k=3.0,
                           repair="clip")
    x_num = impute_by_mean(input_tensor("Xclip", x_num))
    for j, name in enumerate(("age", "income", "tenure")):
        dt.columns[dt.names.index(name)] = x_num[:, j]

    # -- feature transforms -> dense matrix
    x, meta = transformencode(dt, {"age": "scale", "income": "scale",
                                   "tenure": "scale",
                                   "region": "dummycode"})
    y = cols["score"][:, None]
    print(f"feature matrix: {x.shape}, columns: {meta.out_names}")

    rt = LineageRuntime(cache=ReuseCache())
    X, Y = input_tensor("X", x), input_tensor("y", y)

    # -- forward feature selection (Example 1: steplm)
    beta_sel, selected = steplm(X, Y, max_features=5, runtime=rt)
    print("steplm selected:", [meta.out_names[i] for i in selected])

    # -- HPO sweep with lineage reuse (Fig. 5 workload).
    # mode='sequential' pins the per-λ-plan + reuse-cache execution this
    # section narrates; the default auto mode would compile the whole
    # grid into one batched vmapped plan (see `parfor` in the README)
    # where gram/xtv never need the cache — computed once in the
    # config-invariant prefix.
    lambdas = np.logspace(-3, 2, 12).tolist()
    betas, losses = grid_search_lm(X, Y, lambdas, runtime=rt,
                                   mode="sequential")
    best = int(np.argmin(losses))
    print(f"best lambda={lambdas[best]:.4f} "
          f"(cache hits so far: {rt.cache.stats.hits})")

    # -- cross-validation with fold-decomposed partial reuse (Fig. 7)
    fx, fy = make_folds(x, y, 5, seed=0)
    cv_betas, cv_errs = cross_validate_lm(fx, fy, reg=lambdas[best],
                                          runtime=rt, mode="sequential")
    print("cv mse per fold:", np.round(cv_errs, 5))
    print("reuse stats:", rt.cache.stats.as_dict())

    # -- persist the winning model WITH its lineage (model versioning)
    ckpt = os.path.join(tmp, "ckpt")
    path = store.save(ckpt, 0, {"beta": betas[:, best:best + 1]},
                      lineage={"lambda": lambdas[best],
                               "features": meta.out_names,
                               "cv_mse": [float(e) for e in cv_errs]})
    print("model checkpointed at:", path)


if __name__ == "__main__":
    main()

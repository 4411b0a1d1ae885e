"""paper-hpo — scale knobs for the paper's own experiments (§5).

Not a transformer: describes the HPO/CV regression workloads of
Fig. 5-7. The paper uses 100K×1K dense (800 MB) / sparsity-0.1 inputs;
`chip_smoke.py` runs lmDS at this size.
"""
from dataclasses import dataclass


@dataclass(frozen=True)
class PaperWorkload:
    rows: int = 100_000
    cols: int = 1_000
    sparsity: float = 0.1
    k_models: tuple = (1, 10, 20, 30, 40, 50, 60, 70)
    n_folds: int = 8


CONFIG = PaperWorkload()

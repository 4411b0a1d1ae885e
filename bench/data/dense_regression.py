"""Dense regression data from the seed: X (rows x cols) standard normal
float64, and y = X beta + noise_std * e with beta and e standard normal.

X is filled in row blocks by a thread pool. Each block draws from a
stream of its own, so the data does not depend on the number of threads.
Returns the host arrays by the names the jobs bind them under.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 16384


def seed_sequence(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % (1 << 64), stream])


def gaussian(ss: np.random.SeedSequence, rows: int, cols: int,
             threads: int) -> np.ndarray:
    out = np.empty((rows, cols))
    nblocks = -(-rows // BLOCK_ROWS)
    kids = ss.spawn(nblocks)

    def fill(i):
        rng = np.random.default_rng(kids[i])
        rng.standard_normal(out=out[i * BLOCK_ROWS:(i + 1) * BLOCK_ROWS])

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(fill, range(nblocks)))
    return out


def make(seed: int, spec: dict, rows: int, cols: int,
         threads: int) -> dict[str, np.ndarray]:
    x = gaussian(seed_sequence(seed, 0), rows, cols, threads)
    rng = np.random.default_rng(seed_sequence(seed, 1))
    beta = rng.standard_normal((cols, 1))
    y = x @ beta + spec["noise_std"] * rng.standard_normal((rows, 1))
    return {"X": x, "y": y}

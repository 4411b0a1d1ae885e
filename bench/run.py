#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of `BENCHMARK.json`'s
`workloads`. The run needs the TPU chips the cell asks for and exits
non-zero without printing a result where JAX finds fewer. The last line
of standard output is the result, one JSON object; the numbers compared
for `correct` close standard error, each beside its limit.

`--rehearse` runs the cell at a tiny size on whatever JAX finds (the
CPU, for the tests) and labels its result as no device number.
`--control` puts the lower-precision reference in the program's place,
to show that the comparison fails it; the benchmark's own runs never
use it.

JAX's persistent compilation cache lives in `<checkout>/.jax_cache`,
so only the first run of a cell in a checkout compiles.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # cache every program, however fast it compiles, so that a warm run
    # finds all of them
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no size cap: a capped cache evicts, and its eviction fails on any
    # entry that lacks an access-time file, leaving that program out
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness
    return harness.main(sys.argv[1:], root=ROOT, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())

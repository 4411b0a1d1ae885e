"""repro.core — the paper's contribution: declarative LA DSL with lineage
tracing, lineage-based reuse, an optimizing compiler, heterogeneous
tensors, and federated tensors (SystemDS, CIDR 2020)."""
import os as _os
from pathlib import Path as _Path

import jax as _jax

# SystemDS's numeric lifecycle semantics are double-precision; the LM
# model zoo uses explicit f32/bf16 dtypes and is unaffected.
_jax.config.update("jax_enable_x64", True)
# The TPU compiler's 64-bit rewrite refuses the frontend attributes the
# Shardy partitioner leaves on multi-device programs: a float64 solve on
# a mesh does not compile. GSPMD partitions the same programs.
_jax.config.update("jax_use_shardy_partitioner", False)

# Persistent compile cache. JAX itself reads JAX_COMPILATION_CACHE_DIR;
# without it the cache lives at one fixed path inside the checkout, so
# every process of this checkout finds what an earlier one compiled.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        str(_Path(__file__).resolve().parents[3] / ".jax_cache"))

from . import ops  # noqa: F401,E402
from .batching import (BatchedPlan, BatchingError,  # noqa: F401
                       compile_batched)
from .compiler import Plan, compile_plan  # noqa: F401
from .dag import LTensor, batch_input, input_tensor  # noqa: F401
from .federated import (FederatedTensor, LocalSite,  # noqa: F401
                        federated_input)
from .jit_cache import clear_jit_cache, get_jit_cache  # noqa: F401
from .reuse import ReuseCache  # noqa: F401
from .runtime import (LineageRuntime, PreparedScript, evaluate,  # noqa: F401
                      get_runtime, lineage_trace, set_runtime, value)
from .segments import Segment  # noqa: F401

"""Device mesh for the sharded execution backend (SystemDS's distributed
runtime as a compiler placement).

A `MeshSpec` names the two logical axes the compiler shards over:

  * ``data``   — rows of X: the paper's distributed (Spark-analogue)
    backend. `compiler.lower_distributed` propagates a row-sharded
    placement over the DAG and lowers partial reductions (gram/xtv/
    colSums/sum) to per-shard compute + `psum` on this axis.
  * ``config`` — the `parfor` bucket axis: `batching.choose_mode` may
    shard the k-configuration batch across devices instead of (on top
    of) vmapping it on one.

The spec is pure compile-time metadata (two ints) so plans can be
compiled, explained, and cost-tested without any devices forced — the
runtime resolves it to a concrete `jax.sharding.Mesh` lazily, per
process. When the host exposes fewer devices than ``data * config``
the resolution *degrades gracefully* (the `safe_spec` contract from
`repro.distributed.sharding`: an axis that does not fit replicates, it
never errors): `jax_mesh()` returns None and the runtime executes
sharded segments through the local-equivalent kernels, bit-compatible
with the sharded path.

CPU repro: run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
to get an 8-device host mesh (see tests/test_sharded.py).
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Optional

DATA_AXIS = "data"
CONFIG_AXIS = "config"


@dataclass(frozen=True)
class MeshSpec:
    """Compile-time mesh description: axis sizes for (data, config)."""

    data: int = 1
    config: int = 1

    def __post_init__(self):
        if self.data < 1 or self.config < 1:
            raise ValueError(
                f"mesh axes must be >= 1, got data={self.data} "
                f"config={self.config}")

    @property
    def ndev(self) -> int:
        return self.data * self.config

    @property
    def shape(self) -> tuple[int, int]:
        return (self.data, self.config)

    def key_tag(self) -> str:
        """Stable identity for jit-cache keys: sharded and local
        executables of one segment body must never collide, nor two
        mesh shapes with each other."""
        return f"d{self.data}xc{self.config}"

    def jax_mesh(self):
        """Resolve to a concrete `jax.sharding.Mesh`, or None when the
        process does not expose enough devices (graceful degradation —
        the caller falls back to local-equivalent execution)."""
        return _resolve_mesh(self.data, self.config)


def _resolve_mesh(data: int, config: int):
    import jax
    devices = jax.devices()
    if data * config > len(devices) or data * config < 2:
        return None
    return _cached_mesh(data, config)


# One Mesh object per (data, config) shape: shard_map closures capture
# the Mesh, and a stable object keeps AOT-compiled executables valid
# across repeated plan executions.
_mesh_cache: dict[tuple[int, int], object] = {}


def _cached_mesh(data: int, config: int):
    got = _mesh_cache.get((data, config))
    if got is None:
        import jax
        import numpy as np
        from jax.sharding import Mesh
        devs = np.asarray(jax.devices()[: data * config], dtype=object)
        got = Mesh(devs.reshape(data, config), (DATA_AXIS, CONFIG_AXIS))
        _mesh_cache[(data, config)] = got
    return got


# ---------------------------------------------------------------------------
# Active-mesh context: compile_plan picks this up, like SystemDS attaching
# a cluster to the compiler session
# ---------------------------------------------------------------------------

_active: Optional[MeshSpec] = None


def set_mesh(spec: Optional[MeshSpec]) -> None:
    global _active
    _active = spec


def get_mesh() -> Optional[MeshSpec]:
    return _active


@contextmanager
def use_mesh(data: int = 1, config: int = 1):
    """Attach a mesh to subsequently compiled plans:

        with use_mesh(data=8):
            betas, losses = grid_search_lm(X, y, lambdas)
    """
    prev = get_mesh()
    set_mesh(MeshSpec(data=data, config=config))
    try:
        yield get_mesh()
    finally:
        set_mesh(prev)


def auto_mesh(config: int = 1) -> MeshSpec:
    """A data-axis mesh over every visible device (config axis fixed)."""
    import jax
    data = max(1, len(jax.devices()) // max(config, 1))
    return MeshSpec(data=data, config=config)

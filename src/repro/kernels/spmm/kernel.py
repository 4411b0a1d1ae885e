"""Pallas TPU kernels for block-sparse matmul/gram (SpMM behind `bcoo`).

TPU adaptation of SystemDS's sparse blocks (DESIGN.md §2a): on TPU,
sparsity exploitation is *block-level*, not value-level — the MXU eats
dense 128×128 tiles, so the win is skipping tiles whose operand blocks
are entirely zero. Each kernel takes a scalar-prefetched int32 block
nonzero-count map (computed once per operand, see `ops.block_mask`) and
gates the MXU work of a grid step on it with `pl.when`:

  * `gram_block_sparse`  — G = X^T X over column tiles of X, skipping
    (k, i)/(k, j) row-block pairs with no nonzeros; upper-triangle only
    (the tsmm trick), mirrored by the wrapper like `kernels.gram`.
  * `spmm_block_sparse`  — Y = X @ W, skipping zero (i, k) blocks of X.
  * `xtv_block_sparse`   — X^T v without materializing t(X), skipping
    zero row blocks.

At density d with uniformly scattered nonzeros most blocks are nonempty,
but ML sparsity is rarely uniform (empty feature column groups, padded
row ranges, graph locality) — block masks capture exactly that case.
Block loads still stream HBM→VMEM (BlockSpec copies are unconditional);
what the mask saves is MXU work, which dominates for gram/SpMM tiles.

`interpret=True` runs the same kernel body on CPU for tests; the
dispatch layer (`ops.py`) uses BCOO math off-TPU, mirroring
`kernels/rwkv6`'s kernel/ops/ref split.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gram.kernel import MXU_PRECISION

DEFAULT_BM = 512
DEFAULT_BN = 256


def _gram_kernel(mask_ref, xi_ref, xj_ref, out_ref):
    """One (i, j, k) step: out += Xi^T @ Xj when both blocks are nonzero."""
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((j >= i) & (mask_ref[k, i] > 0) & (mask_ref[k, j] > 0))
    def _accum():
        out_ref[...] += jax.lax.dot_general(
            xi_ref[...], xj_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=MXU_PRECISION,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gram_block_sparse(x: jnp.ndarray, mask: jnp.ndarray, *,
                      bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      interpret: bool = False) -> jnp.ndarray:
    """Upper-triangle block-sparse gram; caller mirrors (see ops)."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    assert mask.shape == (m // bm, n // bn), (mask.shape, m // bm, n // bn)
    n_i = n // bn
    grid = (n_i, n_i, m // bm)
    in_specs = [
        pl.BlockSpec((bm, bn), lambda i, j, k, *_: (k, i)),
        pl.BlockSpec((bm, bn), lambda i, j, k, *_: (k, j)),
    ]
    out_spec = pl.BlockSpec((bn, bn), lambda i, j, k, *_: (i, j))
    out_shape = jax.ShapeDtypeStruct((n, n), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=in_specs, out_specs=out_spec)
    return pl.pallas_call(_gram_kernel, grid_spec=grid_spec,
                          out_shape=out_shape,
                          interpret=interpret)(mask, x, x)


def _spmm_kernel(mask_ref, x_ref, w_ref, out_ref):
    """One (i, k) step of Y = X @ W: out_i += X[i,k] @ W[k] if nonzero."""
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(mask_ref[i, k] > 0)
    def _accum():
        out_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=MXU_PRECISION,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def spmm_block_sparse(x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray, *,
                      bm: int = DEFAULT_BM, bk: int = DEFAULT_BN,
                      interpret: bool = False) -> jnp.ndarray:
    """Y = X @ W with zero blocks of X skipped (W columns ride whole)."""
    m, kdim = x.shape
    kw, c = w.shape
    assert kdim == kw and m % bm == 0 and kdim % bk == 0, (x.shape, w.shape)
    assert mask.shape == (m // bm, kdim // bk)
    grid = (m // bm, kdim // bk)
    # block indices must be int32: a bare literal 0 traces as int64
    # under x64, which Mosaic cannot lower
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, k, *_: (i, k)),
        pl.BlockSpec((bk, c), lambda i, k, *_: (k, jnp.int32(0))),
    ]
    out_spec = pl.BlockSpec((bm, c), lambda i, k, *_: (i, jnp.int32(0)))
    out_shape = jax.ShapeDtypeStruct((m, c), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=in_specs, out_specs=out_spec)
    return pl.pallas_call(_spmm_kernel, grid_spec=grid_spec,
                          out_shape=out_shape,
                          interpret=interpret)(mask, x, w)


def _xtv_kernel(mask_ref, x_ref, v_ref, out_ref):
    """One (i, k) step of X^T v: out_i += X[k,i]^T @ v[k] if nonzero."""
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(mask_ref[k, i] > 0)
    def _accum():
        out_ref[...] += jax.lax.dot_general(
            x_ref[...], v_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=MXU_PRECISION,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def xtv_block_sparse(x: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray, *,
                     bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                     interpret: bool = False) -> jnp.ndarray:
    """X^T v with zero row blocks of X skipped (no t(X) materialized)."""
    m, n = x.shape
    mv, c = v.shape
    assert m == mv and m % bm == 0 and n % bn == 0, (x.shape, v.shape)
    assert mask.shape == (m // bm, n // bn)
    grid = (n // bn, m // bm)
    in_specs = [
        pl.BlockSpec((bm, bn), lambda i, k, *_: (k, i)),
        pl.BlockSpec((bm, c), lambda i, k, *_: (k, jnp.int32(0))),
    ]
    out_spec = pl.BlockSpec((bn, c), lambda i, k, *_: (i, jnp.int32(0)))
    out_shape = jax.ShapeDtypeStruct((n, c), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=in_specs, out_specs=out_spec)
    return pl.pallas_call(_xtv_kernel, grid_spec=grid_spec,
                          out_shape=out_shape,
                          interpret=interpret)(mask, x, v)

"""Pallas TPU kernel for gram (tsmm): G = X^T X, and fused X^T v.

This is the paper's single hottest operator (lmDS's X^T X / X^T y, §5.2).
TPU adaptation (DESIGN.md §2): SystemDS's JNI-BLAS dsyrk becomes an
MXU-tiled Pallas kernel:

  * grid = (n/bn, n/bn, m/bm); the k axis (rows of X) is the innermost
    reduction so the f32 output tile stays resident in VMEM across the
    sweep (block revisiting), accumulating in f32.
  * both operands are *column tiles of the same matrix* — two BlockSpecs
    index the same input with different maps, so X streams HBM→VMEM
    without ever materializing t(X).
  * only upper-triangle output tiles (j >= i) are computed (SystemML's
    tsmm trick); the wrapper mirrors them, halving MXU work.

Block sizes default to (bm, bn) = (512, 256): VMEM footprint =
2·bm·bn·2B (bf16 inputs) + bn·bn·4B (f32 acc) ≈ 780 KB « 16 MB VMEM,
and every matmul dim is a multiple of the 128×128 MXU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 512
DEFAULT_BN = 256
# f32 tiles (the DSL's float64 values, cast at the kernel boundary) must
# multiply in f32: the MXU's default is one bf16 pass, which left 3e-4
# relative error in lmDS's beta on a v5e. bf16 tiles take one pass
# either way.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def _gram_kernel(xi_ref, xj_ref, out_ref):
    """One (i, j, k) grid step: out += Xi^T @ Xj for upper-triangle tiles."""
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j >= i)  # lower-triangle tiles are mirrored by the wrapper
    def _accum():
        out_ref[...] += jax.lax.dot_general(
            xi_ref[...], xj_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),  # contract over rows
            precision=MXU_PRECISION,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gram_pallas(x: jnp.ndarray, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                interpret: bool = False) -> jnp.ndarray:
    """Upper-triangle gram via Pallas; caller mirrors (see ops.gram)."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    n_i = n // bn
    out = pl.pallas_call(
        _gram_kernel,
        grid=(n_i, n_i, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, i)),  # Xi column tile
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),  # Xj column tile
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(x, x)
    return out


def _xtv_kernel(x_ref, v_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        x_ref[...], v_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def xtv_pallas(x: jnp.ndarray, v: jnp.ndarray, *, bm: int = DEFAULT_BM,
               bn: int = DEFAULT_BN, interpret: bool = False) -> jnp.ndarray:
    """X^T v (v may have multiple columns; pad columns to the lane width)."""
    m, n = x.shape
    mv, c = v.shape
    assert m == mv and m % bm == 0 and n % bn == 0, (x.shape, v.shape, bm, bn)
    out = pl.pallas_call(
        _xtv_kernel,
        grid=(n // bn, m // bm),
        # block indices must be int32: a bare literal 0 traces as int64
        # under x64, which Mosaic cannot lower
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, k: (k, i)),
            pl.BlockSpec((bm, c), lambda i, k: (k, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((bn, c), lambda i, k: (i, jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        interpret=interpret,
    )(x, v)
    return out

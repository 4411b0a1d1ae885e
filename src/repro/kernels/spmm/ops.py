"""Dispatching wrappers for the block-sparse SpMM/gram kernel family.

These are the kernels registered behind the `bcoo` physical format in
`repro.core.backend` — every function takes/returns jax values and is
fully jit-traceable, so fused segments trace straight through them:

  * TPU            — densify to the block layout, compute the int32
                     block-nonzero map, run the Pallas kernel with the
                     map scalar-prefetched (block-level sparsity:
                     zero-block MXU work is skipped)
  * CPU/GPU        — BCOO math (sparse-dense dot_general), value-level
  * interpret=True — Pallas kernel body interpreted on CPU (tests)
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

# shared with the dense gram kernel family: backend detection, block
# padding, and the upper-triangle mirror must not drift between the
# dense and block-sparse paths
from repro.kernels.gram.ops import (_mirror_upper, _on_tpu, _pad_to,
                                    _to_kernel_dtype)

from . import kernel


def block_mask(x: jnp.ndarray, bm: int, bn: int) -> jnp.ndarray:
    """Traceable int32 per-block nonzero counts of a padded dense x."""
    m, n = x.shape
    blocks = x.reshape(m // bm, bm, n // bn, bn)
    return jnp.count_nonzero(blocks, axis=(1, 3)).astype(jnp.int32)


def gram_dense_masked(xd: jnp.ndarray, *, bm: int = kernel.DEFAULT_BM,
                      bn: int = kernel.DEFAULT_BN,
                      interpret: bool = False) -> jnp.ndarray:
    """Block-masked gram over a dense-layout matrix (the TPU path)."""
    n = xd.shape[1]
    xp = _pad_to(_to_kernel_dtype(xd), bm, bn)
    mask = block_mask(xp, bm, bn)
    g = kernel.gram_block_sparse(xp, mask, bm=bm, bn=bn,
                                 interpret=interpret)
    return _mirror_upper(g, bn)[:n, :n]


def spmm_dense_masked(xd: jnp.ndarray, w: jnp.ndarray, *,
                      bm: int = kernel.DEFAULT_BM,
                      bk: int = kernel.DEFAULT_BN,
                      interpret: bool = False) -> jnp.ndarray:
    """Block-masked X @ W over a dense-layout X (the TPU path)."""
    m, c = xd.shape[0], w.shape[1]
    lane = 128
    xp = _pad_to(_to_kernel_dtype(xd), bm, bk)
    wp = _pad_to(_to_kernel_dtype(w), bk, lane)
    mask = block_mask(xp, bm, bk)
    out = kernel.spmm_block_sparse(xp, wp, mask, bm=bm, bk=bk,
                                   interpret=interpret)
    return out[:m, :c]


def xtv_dense_masked(xd: jnp.ndarray, v: jnp.ndarray, *,
                     bm: int = kernel.DEFAULT_BM,
                     bn: int = kernel.DEFAULT_BN,
                     interpret: bool = False) -> jnp.ndarray:
    """Block-masked X^T v over a dense-layout X (the TPU path)."""
    n, c = xd.shape[1], v.shape[1]
    lane = 128
    xp = _pad_to(_to_kernel_dtype(xd), bm, bn)
    vp = _pad_to(_to_kernel_dtype(v), bm, lane)
    mask = block_mask(xp, bm, bn)
    out = kernel.xtv_block_sparse(xp, vp, mask, bm=bm, bn=bn,
                                  interpret=interpret)
    return out[:n, :c]


# -- BCOO entry points (the backend's bcoo-format kernels) -------------------

def gram_bcoo(x, *, use_pallas: Optional[bool] = None,
              interpret: bool = False):
    """G = X^T X for BCOO X."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas or interpret:
        return gram_dense_masked(x.todense(), interpret=interpret)
    # sparse-dense: flops ∝ nnz·n (sparse-sparse lowering is slow)
    return x.T @ x.todense()


def xtv_bcoo(x, v, *, use_pallas: Optional[bool] = None,
             interpret: bool = False):
    """X^T v for BCOO X, dense v."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if (use_pallas or interpret) and getattr(v, "ndim", 2) == 2:
        return xtv_dense_masked(x.todense(), v, interpret=interpret)
    return x.T @ v


def matmul_bcoo(a, b, *, use_pallas: Optional[bool] = None,
                interpret: bool = False):
    """A @ B for BCOO A, dense B."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if (use_pallas or interpret) and getattr(b, "ndim", 2) == 2:
        return spmm_dense_masked(a.todense(), b, interpret=interpret)
    return a @ b

"""Dispatching wrappers for the gram/tsmm kernel family.

`gram(x)` / `xtv(x, v)` pick the execution path:
  * TPU            — Pallas kernel (upper-triangle + mirror for gram)
  * CPU/GPU        — jnp fallback (XLA dot), f64-capable
  * interpret=True — Pallas kernel body interpreted on CPU (tests)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel, ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _to_kernel_dtype(x: jnp.ndarray) -> jnp.ndarray:
    """Cast a float64 operand to f32 for the Pallas path.

    Mosaic takes no 64-bit operands, while the DSL computes in float64
    (x64 is on process-wide). The kernels accumulate in f32 and return
    f32 either way, so f32 tiles lose only input rounding."""
    return x.astype(jnp.float32) if x.dtype == jnp.float64 else x


def _pad_to(x: jnp.ndarray, bm: int, bn: int) -> jnp.ndarray:
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _mirror_upper(g: jnp.ndarray, bn: int) -> jnp.ndarray:
    """Combine upper-triangle block results into the full symmetric gram.

    Blocks strictly above the diagonal are computed once; their transpose
    fills the lower triangle. Diagonal blocks are complete already.
    """
    n = g.shape[0]
    bi = jnp.arange(n) // bn
    upper_strict = bi[:, None] < bi[None, :]
    # strict-lower blocks of g are zero; fill them with the upper transpose
    return g + jnp.where(upper_strict, g, 0).T


def gram(x, *, use_pallas: Optional[bool] = None, interpret: bool = False,
         bm: int = kernel.DEFAULT_BM, bn: int = kernel.DEFAULT_BN):
    """G = X^T X (f32 accumulation on the kernel path)."""
    x = jnp.asarray(x)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        return ref.gram(x)
    n = x.shape[1]
    xp = _pad_to(_to_kernel_dtype(x), bm, bn)
    g = kernel.gram_pallas(xp, bm=bm, bn=bn, interpret=interpret)
    g = _mirror_upper(g, bn)
    return g[:n, :n]


def xtv(x, v, *, use_pallas: Optional[bool] = None, interpret: bool = False,
        bm: int = kernel.DEFAULT_BM, bn: int = kernel.DEFAULT_BN):
    """X^T v fused (no transpose materialization on the kernel path)."""
    x = jnp.asarray(x)
    v = jnp.asarray(v)
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        out = ref.xtv(x, v)
        return out[:, 0] if squeeze else out
    n, c = x.shape[1], v.shape[1]
    lane = 128
    xp = _pad_to(_to_kernel_dtype(x), bm, bn)
    vp = _pad_to(_to_kernel_dtype(v), bm, lane)
    out = kernel.xtv_pallas(xp, vp, bm=bm, bn=bn, interpret=interpret)
    out = out[:n, :c]
    return out[:, 0] if squeeze else out


def gram_aug(x, y, **kw):
    """One-pass sufficient statistics for lmDS: gram([X|y]) =
    [[X^T X, X^T y], [y^T X, y^T y]] — beyond-paper fusion (DESIGN.md §5)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if y.ndim == 1:
        y = y[:, None]
    return gram(jnp.concatenate([x, y], axis=1), **kw)

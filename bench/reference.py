"""Plain references of the benchmark's comparisons, and their control.

The references are float64 NumPy, written from the definitions:
ridge regression ``beta = solve(X^T X + lambda I, X^T y)`` and its
training loss ``sum((y - X beta)^2)``. They import
nothing of the program under test.

The control is the reference put in the program's place and computed
one precision step below what the configuration states: the products
at ``high`` (three bf16 passes: hi*hi + hi*lo + lo*hi, float32
accumulation, spelled out so that every backend computes the same
thing) where the program's kernels multiply float32 tiles at
``highest``, and float32 where the program computes in float64.
"""
from __future__ import annotations

import numpy as np

CONTROL_BLOCK_ROWS = 65536


def fit(x: np.ndarray, y: np.ndarray, lams, with_loss: bool):
    """float64 betas (d x k, one column per lambda) and losses."""
    g, b = x.T @ x, x.T @ y
    eye = np.eye(x.shape[1])
    betas = np.concatenate([np.linalg.solve(g + lam * eye, b)
                            for lam in lams], axis=1)
    losses = None
    if with_loss:
        losses = [float(np.sum((y - x @ betas[:, i:i + 1]) ** 2))
                  for i in range(len(lams))]
    return betas, losses


def beta_err(got, ref) -> float:
    """Largest column error, each relative to its column's largest
    reference entry."""
    got = np.asarray(got, np.float64).reshape(ref.shape)
    return float(np.max(np.max(np.abs(got - ref), axis=0)
                        / np.max(np.abs(ref), axis=0)))


def loss_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


# -- control ---------------------------------------------------------------

def _bf16(a):
    """Round float32 to bfloat16's precision, kept as float32 (a rounding
    the compiler may not drop, as it may drop a cast pair)."""
    import jax
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dot_high(a, b):
    """a @ b in three bf16 passes: hi*hi + hi*lo + lo*hi, each product of
    bf16 values exact in float32, accumulated in float32."""
    import jax
    import jax.numpy as jnp
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)

    def d(p, q):
        return jnp.dot(p, q, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    return d(a_hi, b_hi) + d(a_hi, b_lo) + d(a_lo, b_hi)


def _blocks(n: int):
    for a in range(0, n, CONTROL_BLOCK_ROWS):
        yield a, min(a + CONTROL_BLOCK_ROWS, n)


def _padded(arr: np.ndarray, a: int, b: int) -> np.ndarray:
    """Rows a:b as float32, zero-padded to a whole block so every block
    runs one compiled program (zero rows add nothing)."""
    out = np.zeros((CONTROL_BLOCK_ROWS,) + arr.shape[1:], np.float32)
    out[:b - a] = arr[a:b]
    return out


_JITTED: dict = {}


def _programs():
    """The control's three programs, built once per process."""
    if not _JITTED:
        import jax
        import jax.numpy as jnp

        def moments(g, v, xb, yb):
            return g + _dot_high(xb.T, xb), v + _dot_high(xb.T, yb)

        def solve(g, v, lam):
            a = g + lam * jnp.eye(g.shape[0], dtype=jnp.float32)
            return jax.scipy.linalg.solve(a, v, assume_a="pos")

        def sq(acc, xb, yb, beta):
            r = yb - _dot_high(xb, beta)
            return acc + jnp.sum(r * r, axis=0)

        _JITTED.update(moments=jax.jit(moments), solve=jax.jit(solve),
                       sq=jax.jit(sq))
    return _JITTED


def control_fit(x: np.ndarray, y: np.ndarray, lams, with_loss: bool):
    """The reference one precision step down, on the default device."""
    import jax.numpy as jnp
    f = _programs()
    n, d = x.shape
    g = jnp.zeros((d, d), jnp.float32)
    v = jnp.zeros((d, 1), jnp.float32)
    for a, b in _blocks(n):
        g, v = f["moments"](g, v, _padded(x, a, b), _padded(y, a, b))
    betas = jnp.concatenate([f["solve"](g, v, jnp.float32(lam))
                             for lam in lams], axis=1)
    losses = None
    if with_loss:
        acc = jnp.zeros((len(lams),), jnp.float32)
        for a, b in _blocks(n):
            acc = f["sq"](acc, _padded(x, a, b), _padded(y, a, b), betas)
        losses = [float(v) for v in np.asarray(acc)]
    return np.asarray(betas, np.float64), losses

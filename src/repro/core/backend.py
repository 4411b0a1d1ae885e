"""Runtime operation library (the TensorBlock operation layer, §3.2/§3.3).

Every HOP is implemented as a *kernel builder*: `attrs -> fn(*inputs)`,
registered in `_KERNEL_BUILDERS`. The returned kernels are pure and
jax-traceable, so the same registry serves two execution modes:

  * standalone   — `execute_op` builds and calls one kernel eagerly
                   (the per-instruction interpreter / `fuse=False` path)
  * fused        — `repro.core.segments.build_segment_fn` chains kernels
                   into one closure per segment and hands it to
                   `jax.jit` (the segment engine)

Two physical representations are supported, mirroring SystemDS's
dense/sparse blocks:

  * dense — jnp arrays (fp64 default on the lifecycle path, like SystemDS)
  * bcoo  — jax.experimental.sparse.BCOO for 2D matrices below the shared
            density threshold (`dag.SPARSE_THRESHOLD`).

Formats are assigned at *compile time* by `repro.core.compiler
.assign_formats` (size/sparsity propagation on the HOP DAG), and kernels
are selected per (op, input formats) at build time — there are no
runtime `is_sparse` branches on the hot path, so BCOO values trace
straight through fused jit segments. Ops without a registered sparse
variant get an automatic densify boundary (`BCOO.todense` is itself a
traceable primitive). `gram`/`xtv` route through `repro.kernels.gram`
(dense Pallas on TPU) and `repro.kernels.spmm` (block-masked sparse
Pallas on TPU; BCOO math elsewhere).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from .dag import SPARSE_THRESHOLD, Node  # single source of truth

# physical format names used across compiler/segments/runtime
DENSE = "dense"
BCOO = "bcoo"

# Minimum element count before a leaf is worth converting to BCOO
# (below this, conversion overhead beats any kernel savings).
SPARSE_MIN_NUMEL = 1 << 12


def is_sparse(x) -> bool:
    return isinstance(x, jsparse.BCOO)


def densify(x):
    return x.todense() if is_sparse(x) else x


def block_ready(x):
    """block_until_ready that also understands BCOO values."""
    buf = x.data if is_sparse(x) else x
    if hasattr(buf, "block_until_ready"):
        buf.block_until_ready()


def _bucket_nse(nse: int) -> int:
    """Round a buffer size up to its power-of-two bucket (min 256)."""
    return 256 if nse <= 256 else 1 << (nse - 1).bit_length()


def sparsify(arr):
    """Eager dense -> BCOO conversion (leaf binding on the bcoo format).

    Built host-side with numpy: `BCOO.fromdense` dispatches a chain of
    eager XLA ops (count/argwhere/gather) costing ~10 ms per bind at
    benchmark sizes — ~100x the numpy scan — and leaf conversion is on
    every call path of a prepared script.

    The nse is padded up to a power-of-two bucket with zero-valued
    duplicates of the last index. nse is part of the BCOO aval — and
    therefore of every fused executable's signature — so without
    bucketing each fresh batch (distinct nnz) would re-trace and
    recompile its segments; with it, batches of similar density share
    warm executables at the cost of ≤ 2x sparse buffer slack. Zero
    padding is exact: BCOO ops treat duplicate indices additively.
    """
    a = np.asarray(arr)
    if a.ndim != 2:  # BCOO leaves are matrices; anything else stays dense
        return jnp.asarray(a)
    rows, cols = np.nonzero(a)
    # np.nonzero is row-major: indices are sorted (and pre-padding,
    # unique) by construction, which lets sparse rules skip a sort
    indices = np.ascontiguousarray(
        np.stack([rows, cols], axis=1).astype(np.int32))
    data = a[rows, cols]
    nse = len(data)
    pad = min(_bucket_nse(nse), a.size) - nse
    if pad > 0:
        tail = indices[-1:] if nse else np.zeros((1, 2), dtype=np.int32)
        indices = np.concatenate([indices, np.repeat(tail, pad, axis=0)])
        data = np.concatenate([data, np.zeros(pad, dtype=data.dtype)])
    # unique_indices is always False so every bind in a bucket carries
    # identical pytree flags — a pad==0 bind must not fork (or collide
    # with) the executables its padded neighbours compiled
    return jsparse.BCOO((jnp.asarray(data), jnp.asarray(indices)),
                        shape=a.shape, indices_sorted=True,
                        unique_indices=False)


def maybe_sparsify(arr, sparsity_est: float):
    """Convert a 2D array to BCOO when the estimate says it pays off.

    Legacy eager heuristic — plan execution now uses the compile-time
    format assignment (`compiler.assign_formats`); this remains for
    standalone/array-level callers.
    """
    if (sparsity_est < SPARSE_THRESHOLD
            and getattr(arr, "ndim", 0) == 2
            and arr.size >= SPARSE_MIN_NUMEL):
        return jsparse.BCOO.fromdense(arr)
    return arr


# ---------------------------------------------------------------------------
# Compile-time format propagation (consumed by compiler.assign_formats)
# ---------------------------------------------------------------------------

# Unary ops with f(0) == 0: applying them to BCOO .data preserves the
# sparsity structure exactly. Single source for both the format rule
# (infer_format) and the sparse kernel registrations below — an op in
# one but not the other would let the compiler assign a BCOO output
# with no kernel to produce it.
_ZERO_PRESERVING_FNS = {
    "neg": jnp.negative, "abs": jnp.abs, "sqrt": jnp.sqrt,
    "sign": jnp.sign, "round": jnp.round, "floor": jnp.floor,
    "ceil": jnp.ceil,
}
ZERO_PRESERVING_UNARY = frozenset(_ZERO_PRESERVING_FNS)


def leaf_format(node: Node) -> str:
    """Physical format for an input leaf, from propagated estimates.

    Federated leaves are bound to `FederatedTensor` metadata objects,
    not arrays — they never take a local physical format. Batched
    leaves (`dag.batch_input`) bind stacked ``(k,)+shape`` arrays that
    flow through `jax.vmap` as dense values — BCOO batch axes are not
    supported on this path."""
    if node.placement != "local":
        return DENSE
    if node.attr("batch") is not None:
        return DENSE
    if (len(node.shape) == 2
            and node.sparsity < SPARSE_THRESHOLD
            and node.numel >= SPARSE_MIN_NUMEL):
        return BCOO
    return DENSE


def bcoo_passthrough_arg(node: Node) -> Optional[int]:
    """Index of the input whose BCOO structure passes through `node`
    unchanged, or None for dense-producing ops.

    The single definition of "structure-preserving" shared by the
    format rule (`infer_format`) and the cost model
    (`costmodel._exec_sparsity`) — one list to extend when a new sparse
    kernel is registered.
    """
    if node.op == "t" or node.op in ZERO_PRESERVING_UNARY:
        return 0
    if node.op == "mul" and len(node.inputs) == 2:
        a, b = node.inputs
        if b.shape == ():  # matrix * scalar keeps the sparse structure
            return 0
        if a.shape == ():
            return 1
    return None


def infer_format(node: Node, in_fmts: tuple[str, ...]) -> str:
    """Output format of one HOP given its input formats.

    Sparse outputs are produced only by ops that preserve the BCOO
    structure for free (see `bcoo_passthrough_arg`); everything else —
    including sparse matmul/gram/xtv, whose products are dense-ish —
    produces dense. Dense never re-sparsifies mid-plan:
    `BCOO.fromdense` inside a trace needs a static nse bound, and a
    wrong estimate would silently drop values.
    """
    if BCOO not in in_fmts:
        return DENSE
    i = bcoo_passthrough_arg(node)
    if i is not None and in_fmts[i] == BCOO:
        return BCOO
    return DENSE


# ---------------------------------------------------------------------------
# op implementations
# ---------------------------------------------------------------------------

def _gram(x):
    from repro.kernels.gram import ops as gram_ops
    return gram_ops.gram(densify(x))


def _xtv(x, v):
    from repro.kernels.gram import ops as gram_ops
    return gram_ops.xtv(densify(x), densify(v))


def _matmul(a, b):
    return densify(a) @ densify(b)


def _solve(a, b):
    a = densify(a).astype(jnp.float64)
    b = densify(b).astype(jnp.float64)
    # SPD fast path (normal equations): cholesky solve, else generic
    return jax.scipy.linalg.solve(a, b, assume_a="pos") \
        if a.shape[0] == a.shape[1] else jnp.linalg.lstsq(a, b)[0]


def _slice(x, index):
    x = densify(x)
    idx = []
    for (start, stop, kind) in index:
        idx.append(start if kind == 1 else slice(start, stop))
    return x[tuple(idx)]


def _colvars(x):
    x = densify(x)
    return jnp.var(x, axis=0, keepdims=True, ddof=1)


_BINARY = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "pow": jnp.power,
    "min2": jnp.minimum, "max2": jnp.maximum,
    "gt": lambda a, b: (a > b).astype(jnp.float32),
    "lt": lambda a, b: (a < b).astype(jnp.float32),
    "ge": lambda a, b: (a >= b).astype(jnp.float32),
    "le": lambda a, b: (a <= b).astype(jnp.float32),
    "eq": lambda a, b: (a == b).astype(jnp.float32),
    "ne": lambda a, b: (a != b).astype(jnp.float32),
    "and": lambda a, b: jnp.logical_and(a != 0, b != 0).astype(jnp.float32),
    "or": lambda a, b: jnp.logical_or(a != 0, b != 0).astype(jnp.float32),
}

_UNARY = {
    "neg": jnp.negative, "exp": jnp.exp, "log": jnp.log, "sqrt": jnp.sqrt,
    "abs": jnp.abs, "sign": jnp.sign, "round": jnp.round,
    "floor": jnp.floor, "ceil": jnp.ceil, "sigmoid": jax.nn.sigmoid,
    "not": lambda x: (x == 0).astype(jnp.float32),
}

_AGG = {
    "sum": jnp.sum, "mean": jnp.mean, "max": jnp.max, "min": jnp.min,
    "trace": jnp.trace,
    "nnz": lambda x: jnp.count_nonzero(x).astype(jnp.float64),
    "colSums": partial(jnp.sum, axis=0, keepdims=True),
    "rowSums": partial(jnp.sum, axis=1, keepdims=True),
    "colMeans": partial(jnp.mean, axis=0, keepdims=True),
    "rowMeans": partial(jnp.mean, axis=1, keepdims=True),
    "colMaxs": partial(jnp.max, axis=0, keepdims=True),
    "colMins": partial(jnp.min, axis=0, keepdims=True),
    "colVars": _colvars,
}


# ---------------------------------------------------------------------------
# Kernel registry: op name -> (attrs -> pure fn(*inputs))
# ---------------------------------------------------------------------------

KernelFn = Any  # Callable[..., array]

_KERNEL_BUILDERS: dict[str, Any] = {}

# Sparse kernel variants, keyed by (op, input format tuple) and mapping
# to (builder, output format). Selected at build time from the
# compile-time format assignment — every entry is a pure jit-traceable
# fn over BCOO/array operands (no eager densify). A variant is only
# picked when its output format matches the one the compiler assigned
# (e.g. `mul(bcoo, scalar)` keeps BCOO, `mul(bcoo, matrix)` falls back
# to the dense kernel through a densify boundary).
_SPARSE_KERNEL_BUILDERS: dict[tuple[str, tuple[str, ...]],
                              tuple[Any, str]] = {}

# Federated instructions (SystemDS §3.3): generated by the compiler's
# placement pass (`repro.core.compiler.lower_federated`), executed by the
# runtime's federated executor — per-site local work runs as compiled
# sub-segments through `LocalSite.execute`, only aggregates cross the
# exchange boundary. They have no entry in the kernel registry: the
# master-side orchestration (site loop + exchange metering) is host
# python, so they are non-traceable by construction.
FED_OPS: frozenset[str] = frozenset({
    "fed_gram", "fed_xtv", "fed_mv", "fed_vm", "fed_colsums", "fed_map",
})
# `collect` is the explicit, cost-modeled federation boundary: it
# materializes a federated value at the master (full partition bytes
# exchanged) so non-lowerable consumers can run locally.
COLLECT_OP = "collect"

# Sharded instructions (the paper's distributed backend as a compiler
# placement): generated by `repro.core.compiler.lower_distributed` when
# a device mesh is attached. Partial-reduction ops compute per-shard on
# the row-sharded operand and `psum` over the mesh's `data` axis;
# `reshard` is the explicit, cost-gated boundary materializing a
# row-sharded value as a replicated one (`all_gather`). They only ever
# trace inside a `jax.shard_map`-wrapped segment
# (`segments.build_sharded_segment_fn`); on hosts without enough
# devices — and on the per-instruction interpreter, which holds global
# arrays — `kernel_for_node(..., unshard=True)` swaps each for its
# local equivalent (`SHARD_BASE_OPS`), which is the 3-way parity oracle.
SHARD_REDUCE_OPS: frozenset[str] = frozenset({
    "shard_gram", "shard_xtv", "shard_colsums", "shard_sum",
})
RESHARD_OP = "reshard"
SHARD_EXEC_OPS: frozenset[str] = SHARD_REDUCE_OPS | {RESHARD_OP}
# local-equivalent op per shard op (None: identity)
SHARD_BASE_OPS: dict[str, Optional[str]] = {
    "shard_gram": "gram", "shard_xtv": "xtv",
    "shard_colsums": "colSums", "shard_sum": "sum", RESHARD_OP: None,
}

# Chunked instructions (out-of-core streaming as a compiler placement):
# generated by `repro.core.compiler.lower_chunked` when a
# row-partitionable reduction's leaves exceed `costmodel
# .CHUNK_MEM_BUDGET`. A `chunk_*` op is a *partial* aggregate — its
# kernel is exactly the base op over whatever rows it is handed, so the
# streaming runtime can run it per-chunk and sum the partials, while
# the per-instruction interpreter (which holds full arrays) gets the
# identical full aggregate from the very same kernel: parity by
# construction, no unshard-style mode flag needed. `combine` is the
# explicit materialization boundary closing the streaming scope — the
# accumulator handoff, an identity on the local path.
CHUNK_PARTIAL_OPS: frozenset[str] = frozenset({
    "chunk_gram", "chunk_xtv", "chunk_colsums", "chunk_sum",
})
COMBINE_OP = "combine"
# local/base-equivalent op per chunk op (None: identity)
CHUNK_BASE_OPS: dict[str, Optional[str]] = {
    "chunk_gram": "gram", "chunk_xtv": "xtv",
    "chunk_colsums": "colSums", "chunk_sum": "sum", COMBINE_OP: None,
}

# Ops that must never be traced into a fused jit segment (data-dependent
# python control flow, host side effects, dynamic output shapes). The
# segmenter isolates them into single-instruction segments which the
# runtime executes eagerly (host path), outside any jit trace:
#   * fed_* / collect — host-side site orchestration + exchange metering
#   * quantile — sort-based order statistics on the host (numpy
#     nanquantile), the control-program analogue of SystemDS's
#     sort-based quantiles; as a DAG node it stays inside the lineage
#     scope, so downstream reuse sees it (unlike an evaluate() round
#     trip that severs lineage mid-pipeline)
NON_TRACEABLE_OPS: frozenset[str] = FED_OPS | {COLLECT_OP, "quantile"}


def register_kernel(op: str):
    """Register `builder(attrs) -> fn(*inputs)` for an op."""
    def deco(builder):
        _KERNEL_BUILDERS[op] = builder
        return builder
    return deco


def register_sparse_kernel(op: str, in_fmts: tuple[str, ...],
                           out_fmt: str = DENSE):
    """Register a sparse variant for (op, input formats) -> out_fmt."""
    def deco(builder):
        _SPARSE_KERNEL_BUILDERS[(op, tuple(in_fmts))] = (builder, out_fmt)
        return builder
    return deco


def has_kernel(op: str) -> bool:
    return op in _KERNEL_BUILDERS


def get_kernel(op: str, attrs: dict[str, Any],
               in_fmts: Optional[tuple[str, ...]] = None,
               out_fmt: str = DENSE) -> KernelFn:
    """Build the pure kernel for one instruction.

    `attrs` is the node's attribute dict plus `_shape` (output shape) for
    generator ops; `in_fmts`/`out_fmt` are the compile-time formats from
    `compiler.assign_formats` (None ≡ all dense). When a BCOO input has a
    registered sparse variant producing the assigned output format it is
    selected here, at build time; any op without one gets the dense
    kernel, whose `densify` calls become traced `BCOO.todense`
    boundaries. The returned fn is closed over static attrs only, so it
    is safe to call standalone or inside a `jax.jit` trace.
    """
    if in_fmts and BCOO in in_fmts:
        entry = _SPARSE_KERNEL_BUILDERS.get((op, tuple(in_fmts)))
        if entry is not None and entry[1] == out_fmt:
            return entry[0](attrs)
    builder = _KERNEL_BUILDERS.get(op)
    if builder is None:
        raise NotImplementedError(f"op {op!r}")
    return builder(attrs)


def _register_table(table: dict[str, Any], arity: int) -> None:
    def make_builder(fn):
        if arity == 1:
            def build(attrs):
                return lambda x: fn(densify(x))
        else:
            def build(attrs):
                return lambda a, b: fn(densify(a), densify(b))
        return build
    for op, fn in table.items():
        _KERNEL_BUILDERS[op] = make_builder(fn)


_register_table(_BINARY, 2)
_register_table(_UNARY, 1)
_register_table(_AGG, 1)


@register_kernel("matmul")
def _build_matmul(attrs):
    return _matmul


@register_kernel("gram")
def _build_gram(attrs):
    return _gram


@register_kernel("xtv")
def _build_xtv(attrs):
    return _xtv


@register_kernel("t")
def _build_t(attrs):
    return lambda x: jnp.transpose(densify(x))


@register_kernel("solve")
def _build_solve(attrs):
    return _solve


# -- sparse (bcoo) kernel variants -------------------------------------------
# All jit-traceable: BCOO matmul/transpose and `todense` are primitives.

@register_sparse_kernel("gram", (BCOO,))
def _sparse_gram(attrs):
    from repro.kernels.spmm import ops as spmm_ops
    return spmm_ops.gram_bcoo


@register_sparse_kernel("xtv", (BCOO, DENSE))
def _sparse_xtv(attrs):
    from repro.kernels.spmm import ops as spmm_ops
    return spmm_ops.xtv_bcoo


@register_sparse_kernel("matmul", (BCOO, DENSE))
def _sparse_matmul(attrs):
    from repro.kernels.spmm import ops as spmm_ops
    return spmm_ops.matmul_bcoo


# (DENSE, BCOO) needs no entry: the dense fallback's densify
# boundary computes the identical dense @ todense(b)
register_sparse_kernel("matmul", (BCOO, BCOO))(
    lambda attrs: (lambda a, b: a @ b.todense()))
register_sparse_kernel("t", (BCOO,), BCOO)(
    lambda attrs: (lambda x: x.T))


def _bcoo_map(fn):
    """Apply a zero-preserving elementwise fn to BCOO values only."""
    def run(x):
        return jsparse.BCOO((fn(x.data), x.indices), shape=x.shape,
                            indices_sorted=x.indices_sorted,
                            unique_indices=x.unique_indices)
    return run


for _op, _fn in _ZERO_PRESERVING_FNS.items():
    register_sparse_kernel(_op, (BCOO,), BCOO)(
        (lambda fn: lambda attrs: _bcoo_map(fn))(_fn))

# only selected when the compiler assigned a BCOO output, i.e. the
# dense operand is a scalar (see infer_format)
register_sparse_kernel("mul", (BCOO, DENSE), BCOO)(
    lambda attrs: (lambda x, s: _bcoo_map(lambda d: d * s)(x)))
register_sparse_kernel("mul", (DENSE, BCOO), BCOO)(
    lambda attrs: (lambda s, x: _bcoo_map(lambda d: s * d)(x)))


# -- sharded (shard_map) kernel variants -------------------------------------
# Pure jax collectives over the mesh axis carried in the node attrs;
# valid only inside a shard_map trace (the sharded segment builder).

@register_kernel("shard_gram")
def _build_shard_gram(attrs):
    axis = attrs.get("axis", "data")
    return lambda x: jax.lax.psum(_gram(x), axis)


@register_kernel("shard_xtv")
def _build_shard_xtv(attrs):
    axis = attrs.get("axis", "data")
    return lambda x, v: jax.lax.psum(_xtv(x, v), axis)


@register_kernel("shard_colsums")
def _build_shard_colsums(attrs):
    axis = attrs.get("axis", "data")
    return lambda x: jax.lax.psum(
        jnp.sum(densify(x), axis=0, keepdims=True), axis)


@register_kernel("shard_sum")
def _build_shard_sum(attrs):
    axis = attrs.get("axis", "data")
    return lambda x: jax.lax.psum(jnp.sum(densify(x)), axis)


@register_kernel(RESHARD_OP)
def _build_reshard(attrs):
    axis = attrs.get("axis", "data")
    return lambda x: jax.lax.all_gather(densify(x), axis, axis=0,
                                        tiled=True)


@register_kernel("cholesky")
def _build_cholesky(attrs):
    return lambda x: jnp.linalg.cholesky(densify(x).astype(jnp.float64))


@register_kernel("inv")
def _build_inv(attrs):
    return lambda x: jnp.linalg.inv(densify(x).astype(jnp.float64))


@register_kernel("diag")
def _build_diag(attrs):
    return lambda x: jnp.diagonal(densify(x))[:, None]


@register_kernel("diagm")
def _build_diagm(attrs):
    return lambda x: jnp.diag(densify(x)[:, 0])


@register_kernel("slice")
def _build_slice(attrs):
    index = attrs["index"]
    return lambda x: _slice(x, index)


@register_kernel("reshape")
def _build_reshape(attrs):
    newshape = attrs["newshape"]
    return lambda x: jnp.reshape(densify(x), newshape)


def _build_concat(attrs):
    axis = attrs["axis"]
    return lambda *xs: jnp.concatenate([densify(x) for x in xs], axis=axis)


_KERNEL_BUILDERS["rbind"] = _build_concat
_KERNEL_BUILDERS["cbind"] = _build_concat


@register_kernel("where")
def _build_where(attrs):
    return lambda c, a, b: jnp.where(densify(c) != 0, densify(a), densify(b))


@register_kernel("replace_nan")
def _build_replace_nan(attrs):
    value = attrs["value"]
    return lambda x: jnp.nan_to_num(densify(x), nan=value)


@register_kernel("cumsum")
def _build_cumsum(attrs):
    return lambda x: jnp.cumsum(densify(x), axis=0)


@register_kernel("quantile")
def _build_quantile(attrs):
    """Host op (in NON_TRACEABLE_OPS): per-column nan-aware quantile via
    numpy's sort-based implementation — must only run on concrete
    values, which the segmenter guarantees by isolating it."""
    q = attrs["q"]

    def run(x):
        arr = np.asarray(densify(x), dtype=np.float64)
        return jnp.asarray(
            np.nanquantile(arr, q, axis=0, keepdims=True))
    return run


@register_kernel("literal")
def _build_literal(attrs):
    value = attrs["value"]
    return lambda: jnp.asarray(value)


@register_kernel("full")
def _build_full(attrs):
    shape, value = attrs.get("_shape", ()), attrs["value"]
    return lambda: jnp.full(shape, value)


@register_kernel("eye")
def _build_eye(attrs):
    n = attrs["_shape"][0]
    return lambda: jnp.eye(n)


@register_kernel("seq")
def _build_seq(attrs):
    n = attrs["_shape"][0]
    start, step = attrs["start"], attrs["step"]
    return lambda: (start + step * jnp.arange(n, dtype=jnp.float64))[:, None]


@register_kernel("rand")
def _build_rand(attrs):
    shape, seed = attrs["_shape"], attrs["seed"]
    dist = attrs.get("dist")
    sp = attrs.get("sparsity_gen", 1.0)

    def run():
        key = jax.random.PRNGKey(seed)
        if dist == "normal":
            out = jax.random.normal(key, shape, dtype=jnp.float64)
        else:
            out = jax.random.uniform(key, shape, dtype=jnp.float64)
        if sp < 1.0:
            key2 = jax.random.PRNGKey(seed + 0x9E3779B9)
            mask = jax.random.uniform(key2, shape) < sp
            out = jnp.where(mask, out, 0.0)
        return out
    return run


@lru_cache(maxsize=4096)
def _kernel_cached(op: str, attrs: tuple, shape: tuple,
                   in_fmts: Optional[tuple], out_fmt: str,
                   unshard: bool = False) -> KernelFn:
    if unshard and op in SHARD_BASE_OPS:
        base = SHARD_BASE_OPS[op]
        if base is None:  # reshard of a global array is the identity
            return lambda x: densify(x)
        op = base
    if op in CHUNK_BASE_OPS:
        # chunk partials ARE the base op over the rows they are handed
        # (full rows on the interpreter, one chunk on the streaming
        # path) — route through the base builder so sparse variants and
        # Pallas kernels apply unchanged
        base = CHUNK_BASE_OPS[op]
        if base is None:  # combine: the accumulator handoff
            return lambda x: densify(x)
        op = base
    d = dict(attrs)
    d["_shape"] = shape
    return get_kernel(op, d, in_fmts=in_fmts, out_fmt=out_fmt)


def kernel_for_node(node, in_fmts: Optional[tuple[str, ...]] = None,
                    out_fmt: str = DENSE, unshard: bool = False) -> KernelFn:
    """Memoized kernel lookup for a HOP node — kernels depend only on
    (op, attrs, shape, formats), so repeated plan executions (the
    interpreter loop, segment lowering) reuse one closure instead of
    rebuilding. `unshard=True` swaps `shard_*`/`reshard` collectives
    for their local equivalents (`SHARD_BASE_OPS`) — the interpreter
    and the no-mesh fallback hold *global* arrays, for which the
    per-shard compute + collective is exactly the base op."""
    return _kernel_cached(node.op, node.attrs, node.shape, in_fmts,
                          out_fmt, unshard)


def execute_op(op: str, attrs: dict[str, Any], inputs: list) -> Any:
    """Execute one instruction eagerly; inputs are jnp arrays (or BCOO)."""
    return get_kernel(op, attrs)(*inputs)


def to_numpy(x) -> np.ndarray:
    return np.asarray(densify(x))

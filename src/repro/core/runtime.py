"""LineageRuntime: the control program (SystemDS §3.2 Fig. 3-3).

Interprets compiled plans instruction-by-instruction, maintains the
intermediate environment (buffer pool with liveness-based frees), traces
lineage for every executed operation, and probes/populates the lineage
reuse cache (§4.1).

Federated plans (§3.3) execute here too: `fed_*` instructions emitted
by the compiler's placement pass loop over the bound `FederatedTensor`'s
sites, run each site's local work as compiled sub-segments
(`LocalSite.execute` -> kernel registry + jit cache), and meter every
byte crossing the federation boundary into `stats.exchange` — per site,
identically across fuse modes.

`PreparedScript` is the JMLC analogue: trace a python function once into
a DAG with placeholder leaves, then re-execute with new in-memory inputs
at low latency (plan is compiled once; lineage is recomputed per input so
reuse stays sound).
"""
from __future__ import annotations

import hashlib
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import numpy as np

from . import backend, costmodel, faults
from .compiler import Plan, compile_plan
from .dag import (LEAVES, LTensor, Node, _fingerprint, _lhash_rec,
                  _slice_fingerprint,
                  input_tensor)  # _fingerprint: PreparedScript lineage
from .faults import CompileFailedError, FaultLog, SiteFailedError
from .federated import ExchangeLog, FederatedTensor, LocalSite
from .jit_cache import get_jit_cache
from .reuse import ReuseCache
from .reuse import nbytes as _reuse_nbytes
from .tracing import SpanLog


@dataclass
class ShardLog:
    """Shard-level analogue of the federation's `ExchangeLog`: counts
    mesh-lowered segment dispatches and the collectives they carry.
    Bytes come from the compile-time cost-model formulas (ring
    all-reduce / all-gather total link bytes over the `data` axis), so
    the meter is deterministic and auditable against exactly the
    exchanges the compiler priced when it accepted each lowering."""

    sharded_segments: int = 0         # shard_map segment dispatches
    config_sharded_segments: int = 0  # bucket-axis (config) dispatches
    reshards: int = 0                 # reshard (all-gather) boundaries run
    collectives: int = 0              # psum-carrying shard_* reduces run
    collective_bytes: int = 0         # total link bytes (cost-model est.)
    placements: int = 0               # host operands placed on the mesh
    placed_bytes: int = 0             # their host bytes

    @property
    def total(self) -> int:
        return (self.sharded_segments + self.config_sharded_segments
                + self.reshards + self.collectives)

    def as_dict(self) -> dict:
        return dict(sharded_segments=self.sharded_segments,
                    config_sharded_segments=self.config_sharded_segments,
                    reshards=self.reshards,
                    collectives=self.collectives,
                    collective_bytes=self.collective_bytes,
                    placements=self.placements,
                    placed_bytes=self.placed_bytes)


@dataclass
class StreamLog:
    """Out-of-core streaming meter (chunked segments, ROADMAP item 4):
    how many row buckets were dispatched vs served from the chunk-level
    lineage cache, the payload bytes moved through device memory, and
    the high-water mark of resident state (the live chunks' inputs plus
    the running partial aggregates) — the quantity the chunk-size
    selection bounds by `costmodel.CHUNK_MEM_BUDGET`."""

    chunked_segments: int = 0  # streaming scopes entered (per run)
    chunks: int = 0            # row-bucket executions dispatched
    chunks_reused: int = 0     # buckets served from chunk-level lineage
    combines: int = 0          # partial-aggregate accumulations
    bytes_streamed: int = 0    # input payload bytes moved per dispatch
    peak_live_bytes: int = 0   # max resident: live chunks + accumulators
    full_hits: int = 0         # whole-stream reuse short-circuits
    device_accumulated: int = 0  # buckets whose partials were summed
                                 # on the device (pipelined dense lane)

    @property
    def total(self) -> int:
        return (self.chunked_segments + self.chunks + self.chunks_reused
                + self.full_hits)

    def as_dict(self) -> dict:
        return dict(chunked_segments=self.chunked_segments,
                    chunks=self.chunks,
                    chunks_reused=self.chunks_reused,
                    combines=self.combines,
                    bytes_streamed=self.bytes_streamed,
                    peak_live_bytes=self.peak_live_bytes,
                    full_hits=self.full_hits,
                    device_accumulated=self.device_accumulated)


@dataclass
class ServingLog:
    """Observability of the `repro.serving` request path: queue/coalesce
    behaviour and the warm-path hygiene counter. Updated by the
    `ModelServer` owning this runtime — all mutation happens on its
    single dispatcher thread, so the counters need no locking."""

    requests: int = 0        # requests scored (excludes rejections)
    batches: int = 0         # coalesced dispatches
    max_coalesce: int = 0    # largest coalesced batch observed
    padded: int = 0          # padding lanes executed (bucket - k waste)
    queue_peak: int = 0      # deepest queue observed at enqueue time
    rejected: int = 0        # bounded-queue rejections (backpressure)
    # jit-cache misses taken by a dispatch AFTER deploy-time warmup.
    # The deploy contract is compile-off-the-request-path: this MUST
    # stay 0 in steady state, and the serving benchmark asserts it.
    retraces: int = 0
    queue_wait_s: float = 0.0  # total enqueue->dispatch delay

    @property
    def total(self) -> int:
        return self.requests + self.rejected

    def as_dict(self) -> dict:
        out = dict(requests=self.requests, batches=self.batches,
                   max_coalesce=self.max_coalesce, padded=self.padded,
                   queue_peak=self.queue_peak, rejected=self.rejected,
                   retraces=self.retraces,
                   queue_wait_s=round(self.queue_wait_s, 6))
        if self.batches:
            out["mean_coalesce"] = round(self.requests / self.batches, 2)
        return out


@dataclass
class PipelineLog:
    """Asynchronous-dispatch meter (the pipelined execution engine that
    closes ROADMAP items 1/2/4's carried "Remaining" bullets).

    At pipeline depth >= 2 the segment executor stops syncing the
    device at segment boundaries. These are counts; the seconds of
    issuing dispatches, blocking on results, prepping buckets and
    copying them to the device are the `ds.dispatch`, `ds.wait`,
    `ds.stream.prep` and `ds.stream.copy` spans (`RuntimeStats.spans`).
    All counters stay 0 at depth 1 (`REPRO_PIPELINE_DEPTH=1`)."""

    async_segments: int = 0   # dispatches returned without a device sync
    prefetch_issued: int = 0  # bucket preps handed to the worker
    prefetch_hits: int = 0    # prepped buckets consumed by a dispatch
    prefetch_cancelled: int = 0  # prepped buckets discarded (cache hit
                                 # raced the prep, or error shutdown)
    device_copies: int = 0    # prepped buckets the worker copied to the
                              # device
    donated_buffers: int = 0  # dead intermediate buffers donated to XLA
    donated_bytes: int = 0    # their payload bytes
    rebatches: int = 0        # serving batches coalesced while another
                              # batch was still in flight

    @property
    def total(self) -> int:
        return (self.async_segments + self.prefetch_issued
                + self.donated_buffers + self.rebatches)

    def as_dict(self) -> dict:
        return dict(async_segments=self.async_segments,
                    prefetch_issued=self.prefetch_issued,
                    prefetch_hits=self.prefetch_hits,
                    prefetch_cancelled=self.prefetch_cancelled,
                    device_copies=self.device_copies,
                    donated_buffers=self.donated_buffers,
                    donated_bytes=self.donated_bytes,
                    rebatches=self.rebatches)


@dataclass
class RuntimeStats:
    instructions: int = 0
    executed: int = 0      # instructions actually computed (not reused)
    reused: int = 0
    segments: int = 0        # segments dispatched on the fused path
    batched_segments: int = 0  # config-variant segments run under vmap
    jit_cache_hits: int = 0  # warm compiled-executable lookups
    compiles: int = 0        # executables compiled (jit-cache misses,
                             # federated site sub-segments included)
    # host seconds and counts per layer boundary (`ds.*` spans, on the
    # profiler's clock while one runs) — see repro.core.tracing
    spans: SpanLog = field(default_factory=SpanLog)
    # bytes crossing the federation boundary (fed_* / collect
    # instructions), metered per site — the §3.3 "exchange constraints"
    # as an auditable budget. Identical across fuse modes by
    # construction: both executors run the same federated instructions
    # and probe the reuse cache at the same compile-time points.
    exchange: ExchangeLog = field(default_factory=ExchangeLog)
    # mesh-lowered execution meter (reshards / collective bytes) — the
    # shard-level analogue of `exchange`
    shard: ShardLog = field(default_factory=ShardLog)
    # request-path meter (queue depth / coalesce sizes / padding waste /
    # hot-path retraces), populated when this runtime backs a
    # `repro.serving.ModelServer`
    serving: ServingLog = field(default_factory=ServingLog)
    # out-of-core streaming meter (chunk dispatches / chunk-level reuse
    # hits / peak resident bytes), populated when the plan contains
    # `lower_chunked`-placed segments
    streaming: StreamLog = field(default_factory=StreamLog)
    # async-dispatch meter (deferred sync / donation / prefetch /
    # rebatching), populated only at pipeline depth >= 2
    pipeline: PipelineLog = field(default_factory=PipelineLog)
    # fault-policy meter (see repro.core.faults): injections observed,
    # retries/timeouts/backoff taken, degradation-ladder steps, serving
    # sheds — plus per-site / per-dispatch latency monitors and site
    # heartbeats (the rescued repro.distributed.fault control plane)
    faults: FaultLog = field(default_factory=FaultLog)

    def as_dict(self):
        out = dict(instructions=self.instructions, executed=self.executed,
                   reused=self.reused, segments=self.segments,
                   batched_segments=self.batched_segments,
                   jit_cache_hits=self.jit_cache_hits,
                   compiles=self.compiles, spans=self.spans.as_dict())
        if self.exchange.total:
            out["exchange"] = self.exchange.as_dict()
        if self.shard.total:
            out["shard"] = self.shard.as_dict()
        if self.serving.total:
            out["serving"] = self.serving.as_dict()
        if self.streaming.total:
            out["streaming"] = self.streaming.as_dict()
        if self.pipeline.total:
            out["pipeline"] = self.pipeline.as_dict()
        if self.faults.total:
            out["faults"] = self.faults.as_dict()
        # the process-wide compiled-executable cache: hit/miss/eviction
        # counters + resident bytes, surfaced here so long-running
        # sessions can watch cache pressure alongside runtime counters
        out["jit_cache"] = get_jit_cache().stats.as_dict()
        return out


@dataclass
class _RunCtx:
    """Per-run execution context of the async pipeline.

    `depth` is the resolved `costmodel.pipeline_depth()` for this run
    (1 = fully synchronous PR-8 behaviour — every gate in the executor
    keys off it). `owned` tracks uids whose CURRENT value is a device
    buffer produced by traced segment execution *this run* and not
    referenced anywhere the runtime cannot see — the run-time half of
    the `donate_argnums` decision: a uid is donatable only while it is
    here, and leaves it the moment the reuse cache takes a reference
    (`put`). Leaf values, cache hits, host-path and chunked outputs are
    never admitted. Kept per-run (not on the runtime) so concurrent
    `run_plan` calls on one runtime cannot alias each other's
    ownership. `leaves` maps each dense registry leaf this run bound
    as a host array to the key of its device copy in the reuse cache
    (`LineageRuntime._upload_keys`); empty without a cache. `placed`
    maps each value a sharded segment read from the host to its shard
    tag and its copy on the mesh (`LineageRuntime._mesh_args`), freed
    with the value."""

    depth: int = 1
    owned: set = field(default_factory=set)
    leaves: dict = field(default_factory=dict)
    placed: dict = field(default_factory=dict)


@dataclass
class _BatchCtx:
    """Execution context of a batched (`parfor`) plan: which value uids
    carry the leading config axis, and how wide the padded axis is."""

    bplan: Any                 # repro.core.batching.BatchedPlan
    batch: int                 # true number of configurations (k)
    bucket: int                # padded batch width (power-of-two)
    bvals: frozenset           # uids with a leading batch axis
    cshard: int = 1            # bucket-axis shards (mesh `config` axis);
                               # 1 = plain vmap, >1 = shard_map over it
    cmesh: Any = None          # resolved jax Mesh when cshard > 1


def _pad_axis0(arr, bucket: int):
    """Re-pad a true-k host/federated result back to the bucket width
    (repeating the last config, like `batching.pad_batch`) so it slots
    into downstream vmapped executables compiled for the bucket."""
    import jax.numpy as jnp
    pad = bucket - arr.shape[0]
    if pad <= 0:
        return arr
    return jnp.concatenate([arr, jnp.repeat(arr[-1:], pad, axis=0)])


class LineageRuntime:
    """Executes plans with lineage tracing and optional reuse."""

    def __init__(self, cache: Optional[ReuseCache] = None,
                 opt_level: int = 2, sparse_inputs: bool = False,
                 fuse: bool = True):
        # sparse_inputs: allow the BCOO physical representation. The
        # compile-time format-assignment pass (compiler.assign_formats)
        # pins each value to dense/bcoo from its sparsity estimate and
        # kernels are selected per format at build time, so sparse plans
        # run through the fused segment engine like dense ones. Default
        # OFF: measured on XLA-CPU, value-level BCOO gram at density 0.1
        # is slower than dense (DESIGN.md §2a, EXPERIMENTS.md §Baseline);
        # on TPU the bcoo format routes to the block-masked Pallas SpMM
        # kernels (repro.kernels.spmm).
        #
        # fuse: execute plans as jit-compiled segments (see
        # repro.core.segments). With an active ReuseCache the segmenter
        # breaks only at cost-gated probe points, and this runtime
        # probes/populates the cache at those boundaries with hit
        # behaviour identical to the fuse=False interpreter.
        self.cache = cache
        self.opt_level = opt_level
        self.sparse_inputs = sparse_inputs
        self.fuse = fuse
        self.stats = RuntimeStats()

    # ------------------------------------------------------------------
    def evaluate(self, outputs: Sequence[LTensor]) -> list[np.ndarray]:
        with self.stats.spans("ds.compile_plan"):
            plan = compile_plan(list(outputs),
                                reuse_enabled=self.cache is not None,
                                opt_level=self.opt_level)
        return self.run_plan(plan)

    # ------------------------------------------------------------------
    def run_plan(self, plan: Plan,
                 leaf_values: Optional[dict[int, Any]] = None,
                 leaf_lineage: Optional[dict[int, str]] = None) -> list[np.ndarray]:
        values, lin = self._bind_leaves(plan, leaf_values, leaf_lineage)
        if self.fuse:
            self._run_segments(
                plan, values, lin,
                rctx=_RunCtx(depth=costmodel.pipeline_depth(),
                             leaves=self._upload_keys(values,
                                                      leaf_values)))
        else:
            self._run_instructions(plan, values, lin)
        # plan roots are THE sync point of the async pipeline (depth
        # >= 2): segment dispatches returned without blocking, so the
        # whole device backlog drains here
        with self.stats.spans("ds.wait"):
            return [backend.to_numpy(values[i]) for i in plan.output_ids]

    # ------------------------------------------------------------------
    def evaluate_batch(self, bplan) -> list[list[np.ndarray]]:
        """Execute a `BatchedPlan` (see `repro.core.batching`): the
        config-invariant prefix runs once through the ordinary segment
        machinery (same executables, same reuse probes as single-config
        plans), config-variant segments run vmapped over the padded
        batch axis. Returns one output list per configuration, in grid
        order, padding sliced off.

        Batched execution is inherently fused — the vmapped suffix IS a
        jit segment — so this path is used regardless of `self.fuse`
        (the interpreter equivalent of a batched plan is the sequential
        per-config loop, which `parfor` falls back to).
        """
        from .batching import pad_batch
        plan = bplan.plan
        bctx = _BatchCtx(bplan=bplan, batch=bplan.batch,
                         bucket=bplan.bucket,
                         bvals=bplan.batched_value_uids)
        if getattr(bplan, "mode", "vmap") == "shard":
            # shard the bucket axis over the mesh's `config` axis;
            # gracefully degrade to plain vmap when the mesh cannot be
            # realized (too few devices) or the bucket does not divide
            ms = getattr(plan, "mesh_spec", None)
            c = int(getattr(ms, "config", 1) or 1) if ms is not None else 1
            jm = ms.jax_mesh() if ms is not None else None
            if c > 1 and jm is not None and bplan.bucket % c == 0:
                bctx.cshard, bctx.cmesh = c, jm
        leaf_values = {
            uid: pad_batch(np.asarray(LEAVES.values[uid]), bplan.bucket)
            for uid in bplan.batched_leaf_uids}
        values, lin = self._bind_leaves(plan, leaf_values, None)
        rctx = _RunCtx(depth=costmodel.pipeline_depth(),
                       leaves=self._upload_keys(values, leaf_values))
        self._run_segments(plan, values, lin, bctx=bctx, rctx=rctx)
        with self.stats.spans("ds.wait"):
            return self._unpack_batch(plan, values, bctx)

    # ------------------------------------------------------------------
    @staticmethod
    def _unpack_batch(plan: Plan, values: dict[int, Any],
                      bctx: _BatchCtx) -> list[list[np.ndarray]]:
        """Split a batched run's outputs into one list per config, in
        order, with the bucket padding sliced off. At pipeline depth
        >= 2 this is the batched path's sync point: the invariant
        prefix and the vmapped variant suffix were all dispatched
        without blocking, and the device backlog drains here."""
        k = bctx.batch
        per_config: list[list[np.ndarray]] = [[] for _ in range(k)]
        for uid in plan.output_ids:
            arr = backend.to_numpy(values[uid])
            if uid in bctx.bvals:
                for j in range(k):
                    per_config[j].append(arr[j])
            else:
                # config-invariant output: every config gets its own
                # copy, matching the sequential path's independent
                # arrays (callers may mutate results in place)
                for j in range(k):
                    per_config[j].append(arr if j == 0 else arr.copy())
        return per_config

    # ------------------------------------------------------------------
    def replay_batch(self, bplan, stacked: Sequence[Any],
                     k: int) -> list[list[np.ndarray]]:
        """Replay a serving `BatchedPlan` (see `batching.compile_serving`)
        on k stacked request bindings — the low-latency scoring entry.

        `stacked` holds one ``(k,) + arg_shape`` array per argument,
        aligned with `bplan.leaf_order`. The batch is padded up to the
        power-of-two bucket (repeating the last request, exactly like
        `parfor`) so every dispatch lands on a warm vmapped executable:
        batch size and bucket are call-time properties — the segment
        set is k-independent and the jit cache re-specializes per
        bucket via the concrete argument signature. Nothing is read
        from or written to the global leaf registry for the request
        leaves, so concurrent plans cannot alias request data; padding
        is sliced off before results are returned.
        """
        from .batching import bucket_size, pad_batch
        plan = bplan.plan
        bucket = bucket_size(k)
        bctx = _BatchCtx(bplan=bplan, batch=k, bucket=bucket,
                         bvals=bplan.batched_value_uids)
        leaf_values = {
            uid: pad_batch(np.asarray(a), bucket)
            for uid, a in zip(bplan.leaf_order, stacked, strict=True)}
        leaf_lineage = None
        if self.cache is not None:
            # reuse probes must key on the request content, not the
            # released placeholder lineage — mirror PreparedScript
            leaf_lineage = {
                uid: f"req:{_fingerprint(np.asarray(a))}"
                for uid, a in zip(bplan.leaf_order, stacked)}
        values, lin = self._bind_leaves(plan, leaf_values, leaf_lineage)
        rctx = _RunCtx(depth=costmodel.pipeline_depth())
        self._run_segments(plan, values, lin, bctx=bctx, rctx=rctx)
        with self.stats.spans("ds.wait"):
            return self._unpack_batch(plan, values, bctx)

    # ------------------------------------------------------------------
    def _bind_leaves(self, plan: Plan,
                     leaf_values: Optional[dict[int, Any]],
                     leaf_lineage: Optional[dict[int, str]]
                     ) -> tuple[dict[int, Any], dict[int, str]]:
        with self.stats.spans("ds.bind"):
            values: dict[int, Any] = {}
            lin: dict[int, str] = {}
            if self.cache is not None:  # lineage only drives reuse probing
                lin = dict(LEAVES.lineage)
                if leaf_lineage:
                    lin.update(leaf_lineage)
            fmts = plan.formats_for(self.sparse_inputs)
            # chunk-sliced leaves consumed ONLY by the streaming lane stay
            # host-resident: the streaming executor sparsifies/uploads one
            # row bucket at a time, so converting the whole leaf up front
            # would materialize exactly what out-of-core execution avoids.
            # A non-chunked consumer (materialization fallback) forces the
            # ordinary device-format bind, and interpreter mode (fuse=False)
            # executes chunk ops eagerly on whole values so it needs it too.
            stream_host: set[int] = set()
            if getattr(plan, "chunk_sliced", None) and self.fuse:
                non_chunk = {u for ins in plan.instructions
                             if ins.target != "chunked"
                             for u in ins.input_ids}
                stream_host = {u for u in plan.chunk_sliced
                               if u not in non_chunk}
            for ins in plan.instructions:
                for inp in ins.node.inputs:
                    if inp.op == "input" and inp.uid not in values:
                        src = None
                        if leaf_values and inp.uid in leaf_values:
                            src = leaf_values[inp.uid]
                        elif inp.uid in LEAVES.values:
                            src = LEAVES.values[inp.uid]
                        else:
                            raise KeyError(
                                f"unbound input leaf {inp.attr('name')}")
                        if isinstance(src, FederatedTensor):
                            # federated leaves bind the metadata object;
                            # partitions never move unless a `collect`
                            # instruction says so
                            values[inp.uid] = src
                            continue
                        # sparsify per bind, never memoized: a cached
                        # conversion cannot detect in-place mutation of the
                        # source array without a full-content scan that
                        # costs as much as the conversion itself
                        arr = np.asarray(src)
                        if (fmts.get(inp.uid) == backend.BCOO
                                and inp.uid not in stream_host):
                            arr = backend.sparsify(arr)
                        values[inp.uid] = arr
            for r in plan.roots:  # outputs that are themselves leaves
                if r.op == "input" and r.uid not in values:
                    # overrides first, registry fallback: a partial
                    # leaf_values dict (batched leaves only, see
                    # evaluate_batch) must not shadow ordinary leaves
                    if leaf_values and r.uid in leaf_values:
                        values[r.uid] = leaf_values[r.uid]
                    else:
                        values[r.uid] = LEAVES.values[r.uid]
            return values, lin

    # ------------------------------------------------------------------
    def _upload_keys(self, values: dict[int, Any],
                     overrides: Optional[dict[int, Any]]
                     ) -> dict[int, tuple]:
        """The dense leaves bound from the registry as host arrays, each
        with the key of its device copy in the reuse cache: the leaf's
        lineage id, dtype and shape. A run's own arrays (a prepared
        script's arguments, a batch's stacks) are not registry leaves
        and are left out."""
        if self.cache is None:
            return {}
        return {u: (LEAVES.lineage[u], a.dtype.str, a.shape)
                for u, a in values.items()
                if isinstance(a, np.ndarray) and LEAVES.values.get(u) is a
                and not (overrides and u in overrides)}

    def _device_args(self, seg, args: list, rctx: _RunCtx) -> list:
        """`args` with each registry leaf replaced by its device copy
        from the reuse cache, copied once under its lineage instead of
        on every call. `values` keeps the host array, which the
        streaming lane slices."""
        if not rctx.leaves:
            return args
        out = list(args)
        for i, u in enumerate(seg.input_uids):
            key = rctx.leaves.get(u)
            if key is not None:
                out[i] = self.cache.leaf_upload(
                    key, out[i], self._upload,
                    out[i].nbytes / costmodel.H2D_BW)
        return out

    def _upload(self, host: np.ndarray):
        import jax
        with self.stats.spans("ds.upload"):
            dev = jax.device_put(host)
            dev.block_until_ready()
        return dev

    def _mesh_args(self, seg, args: list, tags: tuple, jmesh,
                   rctx: _RunCtx) -> list:
        """`args` of a sharded segment with each host array placed on
        the mesh by its shard tag ('s': rows split on the data axis,
        'r': replicated), once a run: a later sharded segment that reads
        the same value gets the placed copy. `values` keeps the host
        array, since a local segment's executable does not take a
        mesh-placed one. A device array is not counted as a placement;
        where its layout differs from the tag's it is moved to the
        tag's layout on the devices, which the cached executable takes
        whatever arguments it was compiled from."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.distributed.mesh import DATA_AXIS
        out = list(args)
        for i, (u, tag) in enumerate(zip(seg.input_uids, tags)):
            got = rctx.placed.get(u)
            a = out[i]
            target = NamedSharding(jmesh, P(DATA_AXIS, None) if tag == "s"
                                   else P())
            if got is not None and got[0] == tag:
                out[i] = got[1]
            elif isinstance(a, jax.Array):
                if not a.sharding.is_equivalent_to(target, a.ndim):
                    out[i] = jax.device_put(a, target)
            elif isinstance(a, np.ndarray):
                with self.stats.spans("ds.shard.place"):
                    dev = self._place(a, target)
                log = self.stats.shard
                log.placements += 1
                log.placed_bytes += a.nbytes
                rctx.placed[u] = (tag, dev)
                out[i] = dev
        return out

    @staticmethod
    def _place(host: np.ndarray, target):
        """`host` on the mesh by the sharding `target`, ready. Each
        device's part larger than `costmodel.PLACE_PIECE_BYTES` crosses
        in row pieces of at most that size, one at a time, and is joined
        on its device: the chip copies one very large array far slower
        than the same bytes in pieces (see the constant)."""
        import jax
        import jax.numpy as jnp
        if host.ndim == 0 or host.nbytes <= costmodel.PLACE_PIECE_BYTES:
            dev = jax.device_put(host, target)
            dev.block_until_ready()
            return dev
        step = max(1, costmodel.PLACE_PIECE_BYTES // host[:1].nbytes)
        shards = []
        for d, idx in target.addressable_devices_indices_map(
                host.shape).items():
            part = host[idx]
            pieces = []
            for r in range(0, part.shape[0], step):
                pieces.append(jax.device_put(part[r:r + step], d))
                pieces[-1].block_until_ready()
            shards.append(pieces[0] if len(pieces) == 1
                          else jnp.concatenate(pieces))
        dev = jax.make_array_from_single_device_arrays(host.shape, target,
                                                       shards)
        dev.block_until_ready()
        return dev

    # ------------------------------------------------------------------
    def _run_instructions(self, plan: Plan, values: dict[int, Any],
                          lin: dict[int, str]) -> None:
        """Per-instruction interpreter (the `fuse=False` fallback);
        probes/populates the reuse cache at cost-gated probe points —
        the same compile-time set the segment executor uses, so hit
        behaviour is identical across both modes."""
        fmts = plan.formats_for(self.sparse_inputs)
        lmemo: dict[int, str] = {}  # lineage-hash memo shared across the run
        for ins in plan.instructions:
            self.stats.instructions += 1
            node = ins.node
            lhash = None
            if self.cache is not None and ins.probe:
                with self.stats.spans("ds.probe"):
                    lhash = _lhash_rec(node, lin, lmemo)
                    hit = self.cache.probe(lhash)
                if hit is not None:
                    values[ins.out_id] = _coerce_format(
                        hit, fmts.get(ins.out_id, backend.DENSE))
                    self.stats.reused += 1
                    self._free(values, ins.last_use_of)
                    continue
            out = self._exec_one(ins, values, fmts)
            self.stats.executed += 1
            values[ins.out_id] = out
            if lhash is not None:
                # admission was decided by the compile-time gate; store
                # the *estimated* cost too — deterministic and identical
                # across fuse modes, so eviction ordering (and therefore
                # hit counts) cannot diverge under pool pressure the way
                # measured wall-times would
                self.cache.put(lhash, out, ins.est_cost_s, gated=False)
            self._free(values, ins.last_use_of)

    # ------------------------------------------------------------------
    def _run_segments(self, plan: Plan, values: dict[int, Any],
                      lin: dict[int, str],
                      bctx: Optional[_BatchCtx] = None,
                      rctx: Optional[_RunCtx] = None) -> None:
        """Segment executor: maximal fusable runs replayed through cached
        jit executables. With an active reuse cache, probe points are
        segment-final (see segments.py): the cache is probed before a
        probe-final segment runs — a hit skips the whole segment — and
        populated from its output afterwards.

        With a `_BatchCtx` (batched `parfor` plans), segmentation is
        variance-aware and config-variant segments execute as
        `jax.vmap`-wrapped executables over the padded batch axis —
        cached under a vmap-tagged key so they never collide with the
        unbatched executable of the same segment body.

        At pipeline depth >= 2 (`_RunCtx.depth`) dispatches return
        without a device sync (XLA computes asynchronously while the
        host walks on to the next segment), and dead-after-segment
        device buffers owned by this run are donated to XLA via
        `donate_argnums` — the donation mask is baked into the jit-
        cache key, so a donated executable can never serve a call whose
        arguments must stay live."""
        if rctx is None:
            rctx = _RunCtx()
        reuse = self.cache is not None
        segments = (bctx.bplan.segments_for(reuse) if bctx is not None
                    else plan.segments_for(reuse))
        fmts = plan.formats_for(self.sparse_inputs)
        jcache = get_jit_cache()
        lmemo: dict[int, str] = {}
        # resolve the plan's mesh once per run; None means not enough
        # devices — sharded segments then run their local-equivalent
        # (unshard) executables, bit-identical in results
        mesh_spec = getattr(plan, "mesh_spec", None)
        jmesh = (mesh_spec.jax_mesh() if mesh_spec is not None else None)
        for seg in segments:
            batched = bctx is not None and seg.variant
            mesh_tags = None    # shard tags of a segment run on the mesh
            self.stats.segments += 1
            if batched:
                self.stats.batched_segments += 1
            self.stats.instructions += len(seg.instructions)
            last = seg.instructions[-1]
            args = [values[u] for u in seg.input_uids]
            seg_key = seg.key
            # physical formats are part of the executable; all-dense
            # segments share one executable across sparse_inputs modes
            # (internal formats derive from the boundary ones)
            boundary = (*seg.input_uids, *seg.output_uids)
            if fmts and any(u in fmts for u in boundary):
                fsig = ",".join(fmts.get(u, backend.DENSE)
                                for u in boundary)
                seg_key = f"{seg_key}|f:{fsig}"
            if getattr(seg, "chunked", False):
                # streaming lane: dispatch the segment once per row
                # bucket and sum the partial aggregates — probes and
                # cache puts happen inside (per output AND per chunk)
                self._run_chunked_segment(plan, seg, seg_key, fmts,
                                          values, lin, lmemo, jcache,
                                          rctx=rctx)
                self._free(values, seg.frees)
                self._free(rctx.placed, seg.frees)
                continue
            if batched:
                axes = "".join("0" if u in bctx.bvals else "-"
                               for u in seg.input_uids)
                seg_key = f"{seg_key}|vmap:{axes}"
                if bctx.cshard > 1:
                    # bucket axis split over the mesh's config axis:
                    # a different executable than plain vmap
                    seg_key = (f"{seg_key}|cshard:{bctx.cshard}x"
                               f"{mesh_spec.key_tag()}")
                    self.stats.shard.config_sharded_segments += 1
            seg_sharded = getattr(seg, "sharded", False)
            # shard_map places its own operands
            local = not seg_sharded and not (batched and bctx.cshard > 1)
            if seg_sharded:
                if jmesh is not None:
                    from .jit_cache import mesh_key_tag
                    from .segments import shard_specs
                    mesh_tags, out_t = shard_specs(seg)
                    seg_key += mesh_key_tag(mesh_spec.key_tag(),
                                            mesh_tags, out_t)
                    self._meter_shard_segment(seg)
                else:
                    seg_key += "|unshard"  # local-equivalent fallback
            lhash = None
            if reuse and last.probe:
                with self.stats.spans("ds.probe"):
                    lhash = _lhash_rec(last.node, lin, lmemo)
                    hit = self.cache.probe(lhash)
                if hit is not None:
                    values[last.out_id] = _coerce_format(
                        hit, fmts.get(last.out_id, backend.DENSE))
                    self.stats.reused += 1
                    rest = tuple(u for u in seg.output_uids
                                 if u != last.out_id)
                    if rest:
                        # multi-output segment: run the compensation
                        # executable — the segment minus the probe value
                        # and everything only it needed — mirroring what
                        # the interpreter computes after the same hit
                        if local:
                            args = self._device_args(seg, args, rctx)
                        elif mesh_tags is not None:
                            args = self._mesh_args(seg, args, mesh_tags,
                                                   jmesh, rctx)
                        self._run_compensation(
                            seg, seg_key, fmts, args, rest, last.out_id,
                            jcache, values,
                            bctx=bctx if batched else None,
                            jmesh=jmesh, rctx=rctx)
                    self._free(values, seg.frees)
                    self._free(rctx.placed, seg.frees)
                    continue
            if last.node.op in backend.NON_TRACEABLE_OPS:
                # host-path segment (always single-instruction): the
                # SAME `_exec_one` the interpreter uses, so fuse modes
                # cannot diverge — federated orchestration / collect
                # boundaries dispatch per-site compiled sub-segments
                # and meter the exchange; other host ops (quantile) run
                # their kernel eagerly, outside any jit trace
                out = self._exec_one(last, values, fmts,
                                     bctx=bctx if batched else None)
                outs = (out,)
                self.stats.executed += 1
            else:
                # note: the REAL bctx, not the variant-gated one — in a
                # batched (parfor/serving) plan even invariant-prefix
                # segments must keep deterministic plain keys
                don = self._donation_mask(seg, values, rctx, bctx)
                if don:
                    # donation changes executable semantics — bake the
                    # mask into the structural key so the donated and
                    # plain executables of one body never collide
                    seg_key = (f"{seg_key}|don:"
                               + ",".join(map(str, don)))
                    plog = self.stats.pipeline
                    plog.donated_buffers += len(don)
                    plog.donated_bytes += sum(
                        _reuse_nbytes(args[i]) for i in don)
                if local:
                    args = self._device_args(seg, args, rctx)
                elif mesh_tags is not None:
                    args = self._mesh_args(seg, args, mesh_tags, jmesh,
                                           rctx)
                try:
                    outs = self._execute_cached(
                        seg_key, self._seg_builder(seg, fmts,
                                                   bctx if batched
                                                   else None,
                                                   jmesh=jmesh),
                        args, jcache, rctx=rctx, donate=don)
                except CompileFailedError as e:
                    # degradation ladder: a segment whose compile took
                    # an injected fault runs its instructions eagerly
                    # through the fuse=False kernels (parity by
                    # construction);
                    # vmapped/sharded segments have no eager equivalent
                    # of the same executable and re-raise
                    e.args = (f"{e.args[0]} [{seg.summary()}]",)
                    if batched or seg_sharded:
                        raise
                    outs = self._interpret_segment(seg, values, fmts)
                else:
                    if rctx.depth >= 2 and not seg_sharded and not (
                            batched and bctx.cshard > 1):
                        # traced outputs this run produced and still
                        # owns — donation candidates for their last
                        # consumer
                        rctx.owned.update(seg.output_uids)
                self.stats.executed += len(seg.instructions)
            for uid, val in zip(seg.output_uids, outs, strict=True):
                values[uid] = val
            if lhash is not None:
                # same estimated cost as the interpreter stores (see
                # _run_instructions) — keeps eviction mode-identical
                self.cache.put(lhash, values[last.out_id],
                               last.est_cost_s, gated=False)
                # the reuse cache now references this buffer: it must
                # never be donated out from under a future hit
                rctx.owned.discard(last.out_id)
            self._free(values, seg.frees)
            self._free(rctx.placed, seg.frees)

    # ------------------------------------------------------------------
    @staticmethod
    def _seg_builder(seg, fmts: dict, bctx: Optional[_BatchCtx],
                     drop_output: Optional[int] = None, jmesh=None):
        """Deferred segment-closure builder (only called on a jit-cache
        miss): plain for invariant segments, vmap-wrapped for
        config-variant ones, shard_map-wrapped for mesh-lowered ones
        (with a local-equivalent fallback when the mesh is absent), and
        shard_map-over-config around the vmap for bucket-sharded
        batched segments."""
        from .segments import (build_batched_segment_fn,
                               build_config_sharded_segment_fn,
                               build_segment_fn, build_sharded_segment_fn)
        if getattr(seg, "sharded", False):
            if jmesh is not None:
                return lambda: build_sharded_segment_fn(
                    seg, fmts, jmesh, drop_output=drop_output)
            return lambda: build_segment_fn(
                seg, fmts, drop_output=drop_output, unshard=True)
        if bctx is None:
            return lambda: build_segment_fn(seg, fmts,
                                            drop_output=drop_output)
        if bctx.cshard > 1 and bctx.cmesh is not None:
            return lambda: build_config_sharded_segment_fn(
                seg, fmts, bctx.bvals, bctx.cmesh,
                drop_output=drop_output)
        return lambda: build_batched_segment_fn(seg, fmts, bctx.bvals,
                                                drop_output=drop_output)

    # ------------------------------------------------------------------
    def _meter_shard_segment(self, seg) -> None:
        """Account one mesh dispatch of a sharded segment into
        `stats.shard` — walked from the compile-time instruction stream,
        so the meter matches the cost model's collective formulas."""
        log = self.stats.shard
        log.sharded_segments += 1
        for ins in seg.instructions:
            op = ins.node.op
            if op == backend.RESHARD_OP:
                log.reshards += 1
                log.collective_bytes += costmodel.collective_bytes(
                    ins.node)
            elif op in backend.SHARD_REDUCE_OPS:
                log.collectives += 1
                log.collective_bytes += costmodel.collective_bytes(
                    ins.node)

    # ------------------------------------------------------------------
    @staticmethod
    def _donation_mask(seg, values: dict[int, Any], rctx: _RunCtx,
                       bctx: Optional[_BatchCtx]) -> tuple[int, ...]:
        """Argument positions safe to donate on this dispatch.

        Structural candidacy (`Segment.donatable_positions`: this
        segment frees the uid, i.e. nothing in the plan reads it
        afterwards) intersected with run-time ownership: the buffer
        must have been produced by traced execution THIS run
        (`_RunCtx.owned` — never a bound leaf, reuse-cache hit, or
        value the cache took a reference to) and be a plain dense
        array (BCOO pytrees and federated handles are never donated).
        Sharded dispatches are excluded wholesale — their buffers live
        on mesh-placed shardings XLA cannot alias into differently-
        placed outputs. Batched (vmap/serving) dispatches are excluded
        too: their donation mask would depend on per-request reuse-probe
        outcomes, and a mask flip changes the executable key — a
        retrace on a pinned serving hot path, which deploy warmup
        guarantees never happens."""
        if rctx.depth < 2 or bctx is not None \
                or getattr(seg, "sharded", False):
            return ()
        cand = seg.donatable_positions()
        if not cand:
            return ()
        return tuple(
            i for i in cand
            if seg.input_uids[i] in rctx.owned
            and not backend.is_sparse(values[seg.input_uids[i]]))

    # ------------------------------------------------------------------
    def _execute_cached(self, seg_key: str, build_fn, args, jcache,
                        rctx: Optional[_RunCtx] = None,
                        donate: tuple = (), span: str = "ds.dispatch"):
        """Run one executable through the jit cache (lookup, compile on
        miss, execute); the call is booked under `span`.

        Pipeline depth 1 (or no `rctx`): block until every output is
        ready — the pre-pipeline behaviour. Depth >= 2: return the
        outputs as in-flight device arrays (XLA dispatches
        asynchronously); the sync happens at plan roots / probe
        materialization / host-op boundaries."""
        spans = self.stats.spans
        key, exe = jcache.lookup(seg_key, args)
        if exe is None:
            try:
                with spans("ds.jit.compile"):
                    exe = jcache.compile(key, build_fn(), args,
                                         donate_argnums=donate)
            except faults.InjectedFault as e:
                # typed so the segment loop can take its degradation
                # ladder (interpreter fallback); a genuine compile error
                # propagates, so a failing device compile is never
                # hidden behind the interpreter
                raise CompileFailedError(seg_key, e) from e
            self.stats.compiles += 1
        else:
            self.stats.jit_cache_hits += 1
        with spans(span):
            outs = exe(*args)
        if rctx is None or rctx.depth < 2:
            with spans("ds.wait"):
                for o in outs:
                    backend.block_ready(o)
        else:
            self.stats.pipeline.async_segments += 1
        return outs

    # ------------------------------------------------------------------
    def _run_compensation(self, seg, seg_key: str, fmts: dict, args,
                          rest: tuple, probe_uid: int, jcache,
                          values: dict[int, Any],
                          bctx: Optional[_BatchCtx] = None,
                          jmesh=None,
                          rctx: Optional[_RunCtx] = None) -> None:
        """Execute a probe-hit segment's remaining outputs (the segment
        with the cached value dead-code eliminated); see
        `segments.build_segment_fn(drop_output=...)`. Never donates —
        the compensation key derives from the plain segment key."""
        try:
            outs = self._execute_cached(
                f"{seg_key}|comp",
                self._seg_builder(seg, fmts, bctx, drop_output=probe_uid,
                                  jmesh=jmesh),
                args, jcache, rctx=rctx)
        except CompileFailedError as e:
            e.args = (f"{e.args[0]} [{seg.summary()}]",)
            if bctx is not None or getattr(seg, "sharded", False):
                raise
            # eager fallback computes ALL segment outputs; deliver only
            # the non-probe ones (the hit already filled probe_uid)
            allouts = self._interpret_segment(seg, values, fmts)
            by_uid = dict(zip(seg.output_uids, allouts, strict=True))
            outs = tuple(by_uid[u] for u in rest)
        # interpreter-equivalent accounting: it would execute every
        # instruction except the one reused (DCE may drop more)
        self.stats.executed += len(seg.instructions) - 1
        for uid, val in zip(rest, outs, strict=True):
            values[uid] = val

    # ------------------------------------------------------------------
    def _interpret_segment(self, seg, values: dict[int, Any],
                           fmts: dict) -> tuple:
        """Graceful-degradation lane for an injected compile fault: run
        the segment's instructions eagerly through `_exec_one` — the
        SAME kernels the fuse=False interpreter dispatches, so the
        degraded result matches the fused executable to numerical
        round-off. Intermediates live in a private overlay; only the
        segment's declared outputs are returned."""
        flog = self.stats.faults
        flog.degradations += 1
        flog.injected += 1
        env = dict(values)  # shallow overlay: refs only
        for ins in seg.instructions:
            env[ins.out_id] = self._exec_one(ins, env, fmts)
        return tuple(env[u] for u in seg.output_uids)

    # ------------------------------------------------------------------
    def _run_chunked_segment(self, plan: Plan, seg, seg_key: str,
                             fmts: dict, values: dict[int, Any],
                             lin: dict[int, str], lmemo: dict[int, str],
                             jcache,
                             rctx: Optional[_RunCtx] = None) -> None:
        """Streaming executor for a chunked-target segment (out-of-core
        execution, ROADMAP item 4).

        The segment's sliced inputs (`plan.chunk_sliced`) are visited in
        row buckets sized by `costmodel.chunk_rows` from the ACTUAL
        per-row payload (BCOO-formatted inputs charged at their sparse
        data+indices size), so one live chunk plus the running partial
        aggregates stay under `costmodel.CHUNK_MEM_BUDGET`. The bucket
        is a power of two independent of the total row count, so every
        full bucket shares ONE warm jit executable (the ragged tail
        compiles a second, once) and appending rows never shifts the
        earlier bucket boundaries.

        Reuse happens at two granularities:

          * full aggregates — each probe-flagged output's lineage hash
            is probed before any chunk is dispatched; when every output
            hits, the whole stream is skipped (the segment-final probe
            of ordinary segments, applied per output);
          * chunk level (incremental recompute) — each bucket's partial
            tuple is cached under a key of the segment structure, the
            row range, and content fingerprints of the bucket's slices
            (plus the replicated operands, which shift every bucket when
            they change). Appending or correcting rows recomputes ONLY
            the affected buckets; untouched ones hit.

        At pipeline depth >= 2 (`costmodel.prefetch_depth`) the stream
        is double-buffered: bucket fingerprints derive from the leaf's
        block-sum table (`dag._slice_fingerprint` — bitwise identical
        to hashing the slice, so the chunk cache is shared across
        depths) and a bounded single-worker thread slices/pads the NEXT
        miss bucket's arguments while the device computes the current
        one. On the dense lane (no BCOO slice, no `concat` output) a
        worker per bucket in flight also copies the slice to the
        device, and the partials
        stay device arrays summed on the device in bucket order: the
        stream makes no host round trip per bucket, and its consumer
        reads the accumulators where they are. Cache lookups, meter
        updates and accumulation stay on the main thread. Worker
        errors propagate to the caller via `Future.result()` and the
        `finally` shutdown cancels queued preps so no thread outlives
        the stream. Depth 1 takes the pre-pipeline loop verbatim.
        """
        import jax.numpy as jnp
        reuse = self.cache is not None
        log = self.stats.streaming
        spans = self.stats.spans
        out_set = set(seg.output_uids)
        out_ins = {ins.out_id: ins for ins in seg.instructions
                   if ins.out_id in out_set}
        # ---- full-aggregate probes (one per probe-flagged output, the
        # same set the fuse=False interpreter probes) ----
        lhashes: dict[int, str] = {}
        hits: dict[int, Any] = {}
        if reuse:
            with spans("ds.probe"):
                for uid in seg.output_uids:
                    if not out_ins[uid].probe:
                        continue
                    lh = _lhash_rec(out_ins[uid].node, lin, lmemo)
                    lhashes[uid] = lh
                    got = self.cache.probe(lh)
                    if got is not None:
                        hits[uid] = got
        # short-circuit iff every output is either a cache-hit partial
        # aggregate or a chunk-invariant generator (a target-neutral
        # literal that rode along) — escaping chunked-placement values
        # have inputs and always force the stream to run
        if hits and all(uid in hits or not out_ins[uid].node.inputs
                        for uid in seg.output_uids):
            for uid in seg.output_uids:
                if uid in hits:
                    values[uid] = _coerce_format(
                        hits[uid], fmts.get(uid, backend.DENSE))
                else:
                    values[uid] = backend.kernel_for_node(
                        out_ins[uid].node)()
            self.stats.reused += len(hits)
            self.stats.executed += len(seg.output_uids) - len(hits)
            log.full_hits += 1
            return

        sliced = [u for u in seg.input_uids if u in plan.chunk_sliced]
        if not sliced:  # defensive: nothing to stream over — the chunk
            # kernels ARE the base ops, so one whole-input dispatch is
            # exact
            outs = self._execute_cached(
                seg_key, self._seg_builder(seg, fmts, None),
                [values[u] for u in seg.input_uids], jcache)
            for uid, val in zip(seg.output_uids, outs, strict=True):
                values[uid] = val
            self.stats.executed += len(seg.instructions)
            return

        log.chunked_segments += 1
        host: dict[int, np.ndarray] = {}
        with spans("ds.wait"):  # free for host leaves; syncs interiors
            for u in sliced:
                a = values[u]
                if backend.is_sparse(a):
                    # materialization fallback for a sparse interior
                    # value entering the stream row-aligned (leaves are
                    # kept host-dense by _bind_leaves; the rare rest)
                    a = a.todense()
                host[u] = np.asarray(a)
        rows = host[sliced[0]].shape[0]
        for u in sliced[1:]:
            if host[u].shape[0] != rows:
                raise ValueError(
                    f"chunked segment {seg.index}: sliced inputs "
                    f"disagree on rows ({host[u].shape[0]} vs {rows})")
        row_bytes = 0.0
        for u in sliced:
            a = host[u]
            if fmts.get(u) == backend.BCOO:
                # BCOO slice payload: data + 2 int32 index columns,
                # charged at 2x for the nse power-of-two padding bucket
                # (see backend.sparsify) — the reuse.nbytes accounting
                nnz = int(np.count_nonzero(a))
                row_bytes += (2.0 * nnz / max(rows, 1)
                              * (a.dtype.itemsize + 8))
            else:
                row_bytes += a.nbytes / max(rows, 1)
        c = costmodel.chunk_rows(row_bytes)
        n_chunks = max(1, -(-rows // c))
        # replicated operands are fingerprinted once: they are part of
        # every chunk's identity (a changed mean shifts every bucket)
        rep_fp = ""
        if reuse:
            with spans("ds.wait"):
                reps = [np.asarray(backend.densify(values[u]))
                        for u in seg.input_uids if u not in host]
            with spans("ds.stream.fingerprint"):
                rep_fp = "|".join(_fingerprint(a) for a in reps)
        cost_each = (sum(i.est_cost_s for i in out_ins.values())
                     / n_chunks)
        builder = self._seg_builder(seg, fmts, None)
        # per-output accumulation mode: chunk_* partials SUM across row
        # buckets; an escaping chunked-placement value (consumed by a
        # later scope through a local boundary) is materialized
        # piecewise — its buckets CONCAT back to the full rows; anything
        # else is a target-neutral generator that rode along and is
        # chunk-invariant — the first bucket's value stands
        modes = {}
        for uid in seg.output_uids:
            n = out_ins[uid].node
            if n.op.startswith("chunk_"):
                modes[uid] = "sum"
            elif n.placement == "chunked":
                modes[uid] = "concat"
            else:
                modes[uid] = "keep"
        accs: dict[int, Any] = {u: None for u in seg.output_uids}

        def _accumulate(parts, live: int, on_device: bool = False) -> None:
            # on_device: the sum is a device add in the partial's dtype,
            # bitwise the host add on IEEE backends; a cached host
            # partial joins the device sum in its place in bucket order
            with spans("ds.stream.combine"):
                for uid, p in zip(seg.output_uids, parts, strict=True):
                    prev = accs[uid]
                    mode = modes[uid]
                    if mode == "concat":
                        accs[uid] = [p] if prev is None else prev + [p]
                    elif prev is None:
                        accs[uid] = p
                    elif mode == "sum":
                        accs[uid] = jnp.add(prev, p) if on_device \
                            else prev + p
                        log.combines += 1
                    # "keep": chunk-invariant — the first value stands
                if on_device:
                    log.device_accumulated += 1
                acc_bytes = sum(_reuse_nbytes(v) for v in accs.values()
                                if v is not None)
                log.peak_live_bytes = max(log.peak_live_bytes,
                                          live + acc_bytes)

        pdepth = 1
        if rctx is not None and rctx.depth >= 2:
            pdepth = costmodel.prefetch_depth(row_bytes, n_chunks)
        if pdepth <= 1:
            # ---- synchronous loop (pre-pipeline behaviour, bitwise
            # and meter identical at REPRO_PIPELINE_DEPTH=1) ----
            for s in range(0, rows, c):
                e = min(s + c, rows)
                parts, ckey, live = None, None, 0
                if reuse:
                    with spans("ds.stream.fingerprint"):
                        fps = ",".join(_fingerprint(host[u][s:e])
                                       for u in sliced)
                    with spans("ds.probe"):
                        ckey = hashlib.sha1(
                            f"chunkpart|{seg_key}|{s}:{e}|{rep_fp}|{fps}"
                            .encode()).hexdigest()
                        parts = self.cache.probe(ckey)
                    if parts is not None:
                        log.chunks_reused += 1
                if parts is None:
                    args = []
                    for u in seg.input_uids:
                        if u in host:
                            a = host[u][s:e]
                            if fmts.get(u) == backend.BCOO:
                                a = backend.sparsify(a)
                            live += _reuse_nbytes(a)
                            args.append(a)
                        else:
                            args.append(values[u])
                    outs = self._execute_cached(seg_key, builder, args,
                                                jcache,
                                                span="ds.stream.dispatch")
                    # partials densify to HOST arrays: their only
                    # consumer is the `combine` densify boundary, numpy
                    # accumulators add chunk-by-chunk regardless of the
                    # slice's format, and host adds skip the per-op
                    # device dispatch that would otherwise dominate
                    # warm (all-chunks-reused) runs
                    with spans("ds.wait"):
                        parts = tuple(np.asarray(backend.densify(o))
                                      for o in outs)
                    log.chunks += 1
                    log.bytes_streamed += live
                    if ckey is not None:
                        self.cache.put(ckey, parts, cost_each,
                                       gated=False)
                _accumulate(parts, live)
        else:
            # the dense lane keeps buckets and partials on the device; a
            # concat output would pile whole rows up there and a BCOO
            # slice is built on the host, so both keep the host path
            on_device = ("concat" not in modes.values() and not any(
                fmts.get(u) == backend.BCOO for u in sliced))
            self._run_chunked_pipelined(
                seg, seg_key, fmts, values, jcache, host, sliced,
                rows, c, reuse, rep_fp, cost_each, builder,
                _accumulate, row_bytes, rctx, on_device)
        with spans("ds.stream.combine"):
            for uid, m in modes.items():
                if m == "concat" and accs[uid] is not None:
                    accs[uid] = np.concatenate(accs[uid], axis=0)
        # cached full aggregates win (identical values, mirrors the
        # interpreter's per-instruction hits); streamed accumulators
        # fill the rest and populate the cache
        for uid in seg.output_uids:
            if uid in hits:
                values[uid] = _coerce_format(
                    hits[uid], fmts.get(uid, backend.DENSE))
            else:
                values[uid] = accs[uid]
                if uid in lhashes:
                    self.cache.put(lhashes[uid], accs[uid],
                                   out_ins[uid].est_cost_s, gated=False)
        self.stats.reused += len(hits)
        self.stats.executed += len(seg.instructions) - len(hits)

    # ------------------------------------------------------------------
    def _run_chunked_pipelined(self, seg, seg_key: str, fmts: dict,
                               values: dict[int, Any], jcache,
                               host: dict[int, np.ndarray],
                               sliced: list, rows: int, c: int,
                               reuse: bool, rep_fp: str,
                               cost_each: float, builder,
                               accumulate, row_bytes: float,
                               rctx: _RunCtx,
                               on_device: bool = False) -> None:
        """Double-buffered bucket loop (pipeline depth >= 2).

        Division of labour, chosen so every shared structure stays
        single-threaded: the MAIN thread resolves each bucket's
        fingerprints (near-free via the leaf's block-sum table when the
        bucket is 4096-byte aligned, direct hashing otherwise — both
        bitwise identical to the synchronous loop's `_fingerprint`, so
        chunk-cache keys and hits are depth-invariant), probes the
        reuse cache, dispatches, accumulates and meters; the single
        WORKER thread only slices/sparsifies a MISS bucket's arguments
        (pure numpy on private data) while the device computes the
        previous bucket. Hit buckets never reach the worker — a warm
        append-retrain stream costs zero wasted copies.

        `on_device` (the dense lane): a worker per bucket in flight
        also copies the slices to the device and waits for the copy
        (`ds.stream.copy`), the main thread calls the executable on the
        device buffers and drops them, and the partials stay device
        arrays, cached as they are and summed on the device, so the
        stream holds no sync at all: the copies run back to back, up
        to `pdepth` at once. Otherwise each bucket's partials come back
        to the host (`ds.wait`) and add in numpy.

        `peak_live_bytes` charges the consuming bucket's actual bytes
        PLUS every in-flight miss bucket's estimated payload, and on the
        dense lane also the previous bucket, whose program may still
        read its device buffer, so the meter honestly reflects the live
        buckets under `CHUNK_MEM_BUDGET` (chunk_rows sizes buckets with
        `CHUNK_LIVE_FACTOR` headroom for exactly this).

        A worker exception surfaces on the main thread at
        `Future.result()`. An injected fault degrades the stream
        mid-flight to the synchronous chunk loop (it costs the
        pipeline, never the answer); any other error propagates.
        Either way the `finally` cancels queued preps (counted as
        `prefetch_cancelled`) and joins the workers, so an error never
        leaves a hung thread or a silently-dropped bucket."""
        import jax
        log = self.stats.streaming
        plog = self.stats.pipeline
        spans = self.stats.spans
        # block-sum tables are only valid when the bound value IS the
        # registered leaf buffer (np.asarray of the registry array is
        # identity); an override/densified copy falls back to hashing
        tables = {}
        for u in sliced:
            a = host[u]
            if (LEAVES.values.get(u) is a
                    and a.flags["C_CONTIGUOUS"] and a.ndim >= 1):
                tables[u] = LEAVES.fp_tables.get(u)
            else:
                tables[u] = None

        def _bucket_fp(u: int, s: int, e: int) -> str:
            sl = host[u][s:e]
            t = tables[u]
            if t is not None:
                fp = _slice_fingerprint(sl, t, s * host[u].strides[0])
                if fp is not None:
                    return fp
            return _fingerprint(sl)

        def _prep(s: int, e: int, probe_faults: bool = True):
            if probe_faults:
                # worker-side injection point: a chunk_io firing here
                # kills this prep — the consumer degrades the rest of
                # the stream to the synchronous loop. The degraded
                # (probe_faults=False) re-preps are injection-free so
                # recovery always completes.
                faults.io_entry("chunk_prefetch")
            args, live = [], 0
            for u in seg.input_uids:
                if u in host:
                    a = host[u][s:e]
                    if fmts.get(u) == backend.BCOO:
                        a = backend.sparsify(a)
                    live += _reuse_nbytes(a)
                    args.append(a)
                else:
                    args.append(values[u])
            return args, live

        def _prep_on_worker(s: int, e: int):
            with spans("ds.stream.prep"):
                args, live = _prep(s, e)
            if on_device:
                with spans("ds.stream.copy"):
                    args = jax.block_until_ready(
                        [jax.device_put(a) if u in host else a
                         for u, a in zip(seg.input_uids, args,
                                         strict=True)])
            return args, live

        bounds = [(s, min(s + c, rows)) for s in range(0, rows, c)]
        pdepth = costmodel.prefetch_depth(row_bytes, len(bounds))
        # the device lane gives each bucket in flight a worker: copies
        # to the device overlap (on a TPU v5e one 64 MB float64 copy
        # moves 1.4 GB/s, two at once 2.7 GB/s together)
        ex = ThreadPoolExecutor(max_workers=pdepth if on_device else 1,
                                thread_name_prefix="chunk-prefetch")
        inflight: deque = deque()  # (s, e, ckey, parts, fut)
        nxt = prev_live = 0
        try:
            while inflight or nxt < len(bounds):
                # keep pdepth buckets resolved/in-flight ahead of the
                # consumer; fingerprint+probe on the main thread, args
                # prep for misses on the worker
                while len(inflight) < pdepth and nxt < len(bounds):
                    s, e = bounds[nxt]
                    nxt += 1
                    ckey, parts, fut = None, None, None
                    if reuse:
                        with spans("ds.stream.fingerprint"):
                            fps = ",".join(_bucket_fp(u, s, e)
                                           for u in sliced)
                        with spans("ds.probe"):
                            ckey = hashlib.sha1(
                                f"chunkpart|{seg_key}|{s}:{e}|{rep_fp}|"
                                f"{fps}".encode()).hexdigest()
                            parts = self.cache.probe(ckey)
                        if parts is not None:
                            log.chunks_reused += 1
                    if parts is None:
                        fut = ex.submit(_prep_on_worker, s, e)
                        plog.prefetch_issued += 1
                    inflight.append((s, e, ckey, parts, fut))
                s, e, ckey, parts, fut = inflight.popleft()
                live = 0
                if parts is None:
                    try:
                        args, live = fut.result()
                    except faults.InjectedFault:
                        # degradation ladder: the prefetch worker died
                        # of an injected fault mid-stream — reclaim this
                        # span plus every queued/unscheduled one and
                        # finish on the synchronous loop (the `finally`
                        # still joins the pool; the sync re-preps are
                        # injection-free, so recovery terminates). A
                        # genuine worker error propagates.
                        flog = self.stats.faults
                        flog.injected += 1
                        flog.degradations += 1
                        tail = [(s, e, ckey, None)]
                        while inflight:
                            s2, e2, ck2, p2, f2 = inflight.popleft()
                            if f2 is not None and f2.cancel():
                                plog.prefetch_cancelled += 1
                            tail.append((s2, e2, ck2, p2))
                        tail.extend((s3, e3, None, None)
                                    for s3, e3 in bounds[nxt:])
                        nxt = len(bounds)
                        for s2, e2, ck2, p2 in tail:
                            live2 = 0
                            if p2 is None and reuse and ck2 is None:
                                # buckets the pipeline never resolved:
                                # probe the chunk cache like the sync
                                # loop would (same keys, same hits)
                                with spans("ds.stream.fingerprint"):
                                    fps = ",".join(_bucket_fp(u, s2, e2)
                                                   for u in sliced)
                                with spans("ds.probe"):
                                    ck2 = hashlib.sha1(
                                        f"chunkpart|{seg_key}|{s2}:{e2}|"
                                        f"{rep_fp}|{fps}"
                                        .encode()).hexdigest()
                                    p2 = self.cache.probe(ck2)
                                if p2 is not None:
                                    log.chunks_reused += 1
                            if p2 is None:
                                args2, live2 = _prep(
                                    s2, e2, probe_faults=False)
                                outs2 = self._execute_cached(
                                    seg_key, builder, args2, jcache,
                                    span="ds.stream.dispatch")
                                with spans("ds.wait"):
                                    p2 = tuple(
                                        np.asarray(backend.densify(o))
                                        for o in outs2)
                                log.chunks += 1
                                log.bytes_streamed += live2
                                if ck2 is not None:
                                    self.cache.put(ck2, p2, cost_each,
                                                   gated=False)
                            accumulate(p2, live2)
                        return
                    plog.prefetch_hits += 1
                    outs = self._execute_cached(seg_key, builder, args,
                                                jcache, rctx=rctx,
                                                span="ds.stream.dispatch")
                    if on_device:
                        # the program holds its operands until it ends:
                        # drop ours (the future keeps them too), so X is
                        # never resident whole
                        plog.device_copies += 1
                        args = fut = None
                        parts = tuple(backend.densify(o) for o in outs)
                    else:
                        with spans("ds.wait"):
                            parts = tuple(np.asarray(backend.densify(o))
                                          for o in outs)
                    log.chunks += 1
                    log.bytes_streamed += live
                    if ckey is not None:
                        self.cache.put(ckey, parts, cost_each,
                                       gated=False)
                # charge the in-flight miss buckets alongside this one
                # (their args are being materialized concurrently) and,
                # on the device lane, the last dispatched one, whose
                # program may still read its buffer
                ahead = sum(int((e2 - s2) * row_bytes)
                            for s2, e2, _, p2, _ in inflight if p2 is None)
                accumulate(parts, live + prev_live + ahead, on_device)
                if on_device and live:
                    prev_live = live
        finally:
            while inflight:
                _, _, _, _, fut = inflight.popleft()
                if fut is not None and fut.cancel():
                    plog.prefetch_cancelled += 1
            ex.shutdown(wait=True)

    # ------------------------------------------------------------------
    def _exec_one(self, ins, values: dict[int, Any], fmts: dict,
                  bctx: Optional[_BatchCtx] = None):
        """Execute one instruction eagerly on concrete values — the
        single implementation shared by the interpreter loop and the
        segment executor's host path (non-traceable singleton
        segments), so cross-mode parity cannot erode: federated ops
        route to the site orchestrator, everything else runs its
        registry kernel with a device sync.

        `bctx` (batched plans) marks operands carrying the config axis:
        federated ops take their batched path (one exchange round for
        the whole grid), other host ops (quantile) loop over the batch
        on the host — they are order-statistics on concrete values and
        cannot vmap."""
        node = ins.node
        if node.op in backend.FED_OPS or node.op == backend.COLLECT_OP:
            return self._exec_federated(ins, values, bctx=bctx)
        kern = backend.kernel_for_node(
            node,
            in_fmts=tuple(fmts.get(u, backend.DENSE)
                          for u in ins.input_ids),
            out_fmt=fmts.get(ins.out_id, backend.DENSE),
            # eager execution holds GLOBAL arrays: shard-exec ops run
            # their local-equivalent base kernels (no collectives)
            unshard=(node.op in backend.SHARD_EXEC_OPS
                     or node.placement == "sharded"))
        spans = self.stats.spans
        if bctx is not None:
            import jax.numpy as jnp
            bpos = {i for i, u in enumerate(ins.input_ids)
                    if u in bctx.bvals}
            args = [values[u] for u in ins.input_ids]
            # host ops loop over the TRUE k, not the padded bucket —
            # padding configs duplicate the last one, so their result
            # is re-padded in, never recomputed
            with spans("ds.dispatch"):
                rows = [kern(*[a[j] if i in bpos else a
                               for i, a in enumerate(args)])
                        for j in range(bctx.batch)]
                out = _pad_axis0(jnp.stack(rows, axis=0), bctx.bucket)
        else:
            with spans("ds.dispatch"):
                out = kern(*[values[u] for u in ins.input_ids])
        with spans("ds.wait"):
            backend.block_ready(out)
        return out

    # ------------------------------------------------------------------
    def _site_call(self, op: str, i: int, rpc, local=None):
        """One federated site RPC under the fault policy: per-site
        timeout + bounded exponential-backoff retry, then the
        degradation ladder. Returns ``(result, degraded)``.

        `rpc(stats)` performs the site call (stats is `self.stats` on
        the first attempt, None on re-attempts so retries cannot
        double-book jit-cache/trace meters); `local()` is the
        collect-and-recompute fallback run when every attempt failed
        but the site's DATA survives. In-process sites cannot be
        preempted, so the timeout binds at the attempt boundary: a
        call slower than `costmodel.FED_TIMEOUT_S` has its (late)
        result discarded and is retried — sound because site kernels
        are pure, a recompute yields the same value. Latencies route
        through the `StepMonitor` straggler flagging and successful
        calls heartbeat the site. With the policy off this is a bare
        passthrough (raw error propagation)."""
        if not faults.policy_enabled():
            return rpc(self.stats), False
        flog = self.stats.faults
        timeout = costmodel.FED_TIMEOUT_S
        last_err: Optional[BaseException] = None
        for attempt in range(costmodel.MAX_RETRIES + 1):
            if attempt:
                pause = costmodel.retry_backoff_s(attempt)
                flog.retries += 1
                flog.backoff_s += pause
                if pause > 0:
                    time.sleep(pause)
            t0 = time.perf_counter()
            try:
                out = rpc(self.stats if attempt == 0 else None)
            except Exception as e:
                flog.record_site(i, time.perf_counter() - t0, ok=False)
                if isinstance(e, faults.InjectedFault):
                    flog.injected += 1
                last_err = e
                continue
            dt = time.perf_counter() - t0
            flog.record_site(i, dt)
            if dt > timeout:
                flog.timeouts += 1
                last_err = TimeoutError(
                    f"site {i} exceeded {timeout}s during {op!r}")
                continue
            return out, False
        plan = faults.active_plan()
        if local is None or (plan is not None and plan.data_lost(i)):
            raise SiteFailedError(i, op, detail=str(last_err))
        flog.degradations += 1
        return local(), True

    def _recompute_local(self, s, i: int, op: str, args: tuple,
                         attrs: tuple, vmap_axes):
        """Degradation-ladder step for a dead site whose data survives:
        pull the partition to the master — metered as a collect
        (`add_in` + one round) — and run the site's work locally
        through the SAME jit-cached executable (`site=None` is never
        injected), so a degraded run is bitwise the clean run."""
        log = self.stats.exchange
        log.add_in(s.data, site=i)
        log.add_round(i)
        return s.execute(op, args, attrs=attrs, stats=self.stats,
                         vmap_axes=vmap_axes, site=None)

    @staticmethod
    def _data_plane_check(op: str, i: int) -> None:
        """Raise `SiteFailedError` when site `i`'s data plane is gone
        (`site_lost`) — guards pure data movement (collect) and the
        recompute ladder, which both read `site.data` directly."""
        if not faults.policy_enabled():
            return
        plan = faults.active_plan()
        if plan is not None and plan.data_lost(i):
            raise SiteFailedError(i, op)

    # ------------------------------------------------------------------
    def _exec_federated(self, ins, values: dict[int, Any],
                        bctx: Optional[_BatchCtx] = None):
        """Execute one federated instruction (or a `collect` boundary).

        Master-side orchestration: loop over sites, run each site's
        local work as a compiled sub-segment (`LocalSite.execute` — the
        kernel registry + process-wide jit cache, so per-site gram runs
        the same Pallas/BCOO kernels as local plans and repeated runs
        replay warm executables), and meter every byte crossing the
        federation boundary into `stats.exchange`, per site. Every
        (instruction, site) pair that actually exchanges bytes counts
        one *round* (`ExchangeLog.add_round`).

        With a `_BatchCtx`, batched *local* operands (fed operands are
        never batched — `batching.choose_mode` guarantees it) travel as
        ONE stacked payload per site and the site's work runs vmapped
        over the config axis: a k-configuration grid costs one round
        per site per instruction, not k.
        """
        node = ins.node
        op = node.op
        log = self.stats.exchange
        args = [values[u] for u in ins.input_ids]
        bpos = (frozenset(i for i, u in enumerate(ins.input_ids)
                          if u in bctx.bvals)
                if bctx is not None else frozenset())

        if op == backend.COLLECT_OP:
            fed = args[0]
            fed._require_sites(op)
            batched = getattr(fed, "batch", None) is not None
            parts = []
            for i, s in enumerate(fed.sites):
                # collect is pure data movement: only a lost DATA plane
                # can fail it (a dead compute plane still serves reads)
                self._data_plane_check(op, i)
                log.add_in(s.data, site=i)
                log.add_round(i)
                parts.append(np.asarray(s.data))
            # batched site layout is (k, rows_i, c): rows concat on axis 1
            out = np.concatenate(parts, axis=1 if batched else 0)
            return _pad_axis0(out, bctx.bucket) if batched else out

        if op == "fed_gram":
            fed = args[0]
            fed._require_sites(op)
            batched = getattr(fed, "batch", None) is not None
            vmap_axes = (0,) if batched else None
            out = None
            for i, s in enumerate(fed.sites):
                g, deg = self._site_call(
                    op, i,
                    lambda st, s=s, i=i: s.execute(
                        "gram", (s.data,), stats=st,
                        vmap_axes=vmap_axes, site=i),
                    local=lambda s=s, i=i: self._recompute_local(
                        s, i, "gram", (s.data,), (), vmap_axes))
                if not deg:  # exchange metered on success only
                    log.add_in(g, site=i)
                    log.add_round(i)
                out = g if out is None else out + g
            return _pad_axis0(out, bctx.bucket) if batched else out

        if op in ("fed_xtv", "fed_vm"):
            # x^T v with any subset of {x, v} federated: per-site
            # partial products summed at the master; row-aligned local
            # operands are sent sliced (only the relevant rows travel).
            # Batched local operands are sliced along the row axis of
            # each config: v[:, a:b] — one stacked send per site.
            fed_pos = set(node.attr("fed_args", (0,)))
            fed = args[min(fed_pos)]
            fed._require_sites(op)
            self._check_alignment(op, [args[p] for p in sorted(fed_pos)])
            # batched positions: local operands flagged by the plan plus
            # federated operands whose site layout carries a config axis
            # (stacked (k, rows_i, c) partitions from a batched fed_map)
            bat = set(bpos) | {p for p in fed_pos
                               if getattr(args[p], "batch", None)}
            # densify local operands once, outside the site loop; a
            # batched operand is sliced to the TRUE k before anything
            # crosses the wire — the bucket padding (duplicates of the
            # last config) exists only to stabilize executable shapes,
            # and must not inflate the exchange
            args = [v if pos in fed_pos else
                    (backend.densify(v)[:bctx.batch] if pos in bat
                     else backend.densify(v))
                    for pos, v in enumerate(args)]
            vmap_axes = (tuple(0 if pos in bat else None
                               for pos in range(len(args)))
                         if bat else None)
            out = None
            for i, (a, b) in enumerate(fed.ranges):
                site_args, sent = [], []
                for pos, v in enumerate(args):
                    if pos in fed_pos:
                        site_args.append(v.sites[i].data)
                    else:
                        sl = v[:, a:b] if pos in bat else v[a:b]
                        sent.append(sl)
                        site_args.append(sl)
                s = fed.sites[i]
                sa = tuple(site_args)
                r, deg = self._site_call(
                    op, i,
                    lambda st, s=s, i=i, sa=sa: s.execute(
                        "xtv", sa, stats=st,
                        vmap_axes=vmap_axes, site=i),
                    local=lambda s=s, i=i, sa=sa: self._recompute_local(
                        s, i, "xtv", sa, (), vmap_axes))
                if not deg:  # exchange metered on success only
                    for sl in sent:
                        log.add_out(sl, site=i)
                    log.add_in(r, site=i)
                    log.add_round(i)
                out = r if out is None else out + r
            return _pad_axis0(out, bctx.bucket) if bat else out

        if op == "fed_mv":
            fed, w = args
            fed._require_sites(op)
            w = backend.densify(w)
            fed_b = getattr(fed, "batch", None) is not None
            w_b = 1 in bpos
            batched = fed_b or w_b
            if w_b:  # send the true k configs, never the padding
                w = w[:bctx.batch]
            vmap_axes = ((0 if fed_b else None, 0 if w_b else None)
                         if batched else None)
            parts = []
            for i, s in enumerate(fed.sites):
                r, deg = self._site_call(
                    op, i,
                    lambda st, s=s, i=i: s.execute(
                        "matmul", (s.data, w), stats=st,
                        vmap_axes=vmap_axes, site=i),
                    local=lambda s=s, i=i: self._recompute_local(
                        s, i, "matmul", (s.data, w), (), vmap_axes))
                if not deg:
                    log.add_out(w, site=i)  # broadcast (whole grid)
                    log.add_in(r, site=i)   # rbind of per-site results
                    log.add_round(i)
                parts.append(np.asarray(r))
            # per-site results are (rows_i, n) — or (k, rows_i, n)
            # batched — so the row concat axis shifts with the batch
            out = np.concatenate(parts, axis=1 if batched else 0)
            return _pad_axis0(out, bctx.bucket) if batched else out

        if op == "fed_colsums":
            fed = args[0]
            fed._require_sites(op)
            batched = getattr(fed, "batch", None) is not None
            vmap_axes = (0,) if batched else None
            out = None
            for i, s in enumerate(fed.sites):
                r, deg = self._site_call(
                    op, i,
                    lambda st, s=s, i=i: s.execute(
                        "colSums", (s.data,), stats=st,
                        vmap_axes=vmap_axes, site=i),
                    local=lambda s=s, i=i: self._recompute_local(
                        s, i, "colSums", (s.data,), (), vmap_axes))
                if not deg:
                    log.add_in(r, site=i)
                    log.add_round(i)
                out = r if out is None else out + r
            return _pad_axis0(out, bctx.bucket) if batched else out

        if op == "fed_map":
            return self._exec_fed_map(node, args, log, bctx=bctx,
                                      bpos=bpos)

        raise NotImplementedError(f"federated op {op!r}")

    def _exec_fed_map(self, node, args: list, log: ExchangeLog,
                      bctx: Optional[_BatchCtx] = None,
                      bpos: frozenset = frozenset()) -> FederatedTensor:
        """Row-preserving op applied per site: the output is a new
        `FederatedTensor` over the same ranges — no aggregate exchange.
        Local operands travel by shape: scalars and `full` generators
        cost nothing (generated on site), broadcast rows go to every
        site, row-aligned matrices are sent sliced.

        Batched (`parfor`) operands — local values flagged by the plan
        (`bpos`) or federated operands already carrying the stacked
        layout — travel as ONE (k, …) payload per site and the site's
        work runs vmapped over the config axis; the output federated
        tensor then carries the stacked (k, rows_i, c) site layout
        (`FederatedTensor.batch`), which the other fed_* instructions'
        batched paths consume. Only the TRUE k crosses the wire."""
        inner = node.attr("inner")
        n_args = node.attr("n_args")
        fed_pos = set(node.attr("fed_args", ()))
        gens = {p: (v, k, dt) for p, v, k, dt in node.attr("gen_args", ())}
        iattrs = dict(node.attr("iattrs", ()))
        slot: dict[int, Any] = {}
        bslots: set[int] = set()  # inner positions carrying the config axis
        it = iter(enumerate(args))
        for pos in range(n_args):
            if pos not in gens:
                ai, v = next(it)
                if pos in fed_pos:
                    slot[pos] = v
                    if getattr(v, "batch", None) is not None:
                        bslots.add(pos)
                else:
                    # densify local operands once, outside the site
                    # loop; batched ones sliced to the TRUE k up front
                    v = backend.densify(v)
                    if ai in bpos:
                        v = v[:bctx.batch]
                        bslots.add(pos)
                    slot[pos] = v
        batched = bool(bslots)
        vmap_axes = (tuple(0 if pos in bslots else None
                           for pos in range(n_args))
                     if batched else None)
        feds = [slot[p] for p in sorted(fed_pos)]
        fed = feds[0]
        fed._require_sites("fed_map")
        self._check_alignment("fed_map", feds)
        new_sites = []
        for i, (a, b) in enumerate(fed.ranges):
            rows_i = b - a
            ia = dict(iattrs)
            if inner == "slice":
                # rebase the absolute row range onto this site's rows
                idx = list(ia["index"])
                idx[0] = (0, rows_i, 0)
                ia["index"] = tuple(idx)
            site_args, to_send = [], []
            for pos in range(n_args):
                if pos in gens:
                    val, k, dt = gens[pos]
                    site_args.append(
                        np.full((rows_i, int(k)), val, dtype=np.dtype(dt)))
                elif pos in fed_pos:
                    site_args.append(slot[pos].sites[i].data)
                else:
                    v = slot[pos]
                    shp = getattr(v, "shape", ())
                    # route by the per-config shape: a batched operand
                    # carries a leading (k, …) axis on top of it
                    ishp = shp[1:] if pos in bslots else shp
                    if ishp == () or ishp[0] == 1:
                        if ishp != () or pos in bslots:
                            to_send.append(v)  # broadcast payload
                        site_args.append(v)
                    else:
                        sl = (v[:, a:b] if pos in bslots else v[a:b])
                        to_send.append(sl)
                        site_args.append(sl)
            s = fed.sites[i]
            sa, attrs = tuple(site_args), tuple(sorted(ia.items()))
            out_i, deg = self._site_call(
                "fed_map", i,
                lambda st, s=s, i=i, sa=sa, attrs=attrs: s.execute(
                    inner, sa, attrs=attrs, stats=st,
                    vmap_axes=vmap_axes, site=i),
                local=lambda s=s, i=i, sa=sa, attrs=attrs:
                    self._recompute_local(s, i, inner, sa, attrs,
                                          vmap_axes))
            if not deg and to_send:
                # purely on-site fed_map work (generators, fed
                # operands) exchanges nothing and counts no round;
                # exchange is metered on success only
                for payload in to_send:
                    log.add_out(payload, site=i)
                log.add_round(i)
            new_sites.append(LocalSite(out_i))
        return FederatedTensor(sites=new_sites, ranges=list(fed.ranges),
                               ncols=node.shape[1],
                               batch=bctx.batch if batched else None)

    @staticmethod
    def _check_alignment(op: str, feds: list) -> None:
        ranges = feds[0].ranges
        for f in feds[1:]:
            if list(f.ranges) != list(ranges):
                raise ValueError(
                    f"{op}: federated operands are partitioned "
                    f"differently ({f.ranges} vs {ranges}); joint "
                    "federated execution requires aligned row ranges")

    @staticmethod
    def _free(values: dict[int, Any], uids: tuple[int, ...]):
        for uid in uids:
            values.pop(uid, None)


def _coerce_format(value: Any, fmt: str) -> Any:
    """Align a reuse-cache hit with the plan's assigned physical format.

    Lineage hashes identify *values*, not representations: a cache
    shared across runtimes (or sparse_inputs settings) can return a
    dense array where this plan assigned BCOO, or vice versa. Sparse
    kernels have no dense guard, so convert at the boundary.
    """
    if fmt == backend.BCOO and not backend.is_sparse(value):
        return backend.sparsify(np.asarray(value))
    if fmt == backend.DENSE and backend.is_sparse(value):
        return value.todense()
    return value


# ---------------------------------------------------------------------------
# Module-level convenience (a default runtime without reuse)
# ---------------------------------------------------------------------------

_default_runtime: Optional[LineageRuntime] = None


def get_runtime() -> LineageRuntime:
    global _default_runtime
    if _default_runtime is None:
        _default_runtime = LineageRuntime()
    return _default_runtime


def set_runtime(rt: LineageRuntime) -> None:
    global _default_runtime
    _default_runtime = rt


def evaluate(*outputs: LTensor, runtime: Optional[LineageRuntime] = None
             ) -> list[np.ndarray]:
    rt = runtime or get_runtime()
    return rt.evaluate(list(outputs))


def value(x: LTensor, runtime: Optional[LineageRuntime] = None) -> np.ndarray:
    return evaluate(x, runtime=runtime)[0]


# ---------------------------------------------------------------------------
# PreparedScript (JMLC-style precompiled script, §3.1)
# ---------------------------------------------------------------------------

class PreparedScript:
    """Compile a DSL function once; execute repeatedly with new inputs."""

    def __init__(self, fn: Callable[..., Any],
                 arg_shapes: Sequence[tuple[int, ...]],
                 arg_dtypes: Optional[Sequence[Any]] = None,
                 runtime: Optional[LineageRuntime] = None,
                 arg_sparsities: Optional[Sequence[float]] = None):
        # arg_sparsities: declared density per argument (JMLC-style
        # metadata). The placeholder leaves are zeros, so without a
        # declaration the format-assignment pass would estimate every
        # leaf as empty and pin it to BCOO; default to dense (1.0) and
        # let callers declare what they will actually bind.
        self.runtime = runtime or get_runtime()
        self._fn = fn
        self._arg_shapes = [tuple(int(d) for d in s) for s in arg_shapes]
        self._arg_dtypes = [np.dtype(d) for d in (
            arg_dtypes or [np.float64] * len(arg_shapes))]
        self._arg_sparsities = list(
            arg_sparsities or [1.0] * len(arg_shapes))
        # shape-variation memo: bound-shapes tuple -> None (accepted) or
        # the rejection message (see _check_shapes)
        self._shape_verdicts: dict[tuple, Optional[str]] = {}
        self._leaves = [
            input_tensor(f"arg{i}", np.zeros(s, dtype=d), sparsity=sp)
            for i, (s, d, sp) in enumerate(
                zip(self._arg_shapes, self._arg_dtypes,
                    self._arg_sparsities, strict=True))]
        outs = fn(*self._leaves)
        if isinstance(outs, LTensor):
            outs = [outs]
        self._outputs = list(outs)
        self.plan = compile_plan(
            self._outputs, reuse_enabled=self.runtime.cache is not None,
            opt_level=self.runtime.opt_level)

    # ------------------------------------------------------------------
    def validate_args(self, arrays: Sequence[Any],
                      exact_shapes: bool = False) -> list[np.ndarray]:
        """Validate bindings against the declared `arg_shapes` /
        `arg_dtypes` — at bind time, with a clear `ValueError`, instead
        of a shape/dtype explosion deep inside segment execution.

        Dtypes: a binding whose dtype safe-casts to the declared one
        (int grids into a float plan) is converted; anything lossy
        (float into an int plan, complex into float) is an error.

        Shapes: a binding may deviate from the declared shape only
        along axes the plan never *constrains* — verified by re-tracing
        the script function at the bound shapes and requiring the same
        instruction stream (see `_check_shapes`); generators (`eye(n)`,
        `ones((m, 1))` intercepts), slice bounds, and shape-dependent
        rewrites all constrain their axes and reject the binding.
        `exact_shapes` (the serving path, which stacks requests into
        fixed buckets) skips the re-trace escape hatch entirely.
        """
        if len(arrays) != len(self._leaves):
            # a real error, not an assert: argument-count bugs must
            # surface under `python -O` too
            raise ValueError(
                f"PreparedScript expects {len(self._leaves)} argument(s), "
                f"got {len(arrays)}")
        out: list[np.ndarray] = []
        mismatch = False
        for i, (arr, shape, dtype) in enumerate(
                zip(arrays, self._arg_shapes, self._arg_dtypes)):
            arr = np.asarray(arr)
            if arr.dtype != dtype:
                if not np.can_cast(arr.dtype, dtype, casting="safe"):
                    raise ValueError(
                        f"PreparedScript arg{i}: bound dtype {arr.dtype} "
                        f"does not safe-cast to the declared {dtype}")
                arr = arr.astype(dtype)
            if arr.shape != shape:
                if exact_shapes or len(arr.shape) != len(shape):
                    raise ValueError(
                        f"PreparedScript arg{i}: bound shape {arr.shape} "
                        f"!= declared {shape}")
                mismatch = True
            out.append(arr)
        if mismatch:
            self._check_shapes(tuple(a.shape for a in out))
        return out

    def _check_shapes(self, shapes: tuple) -> None:
        """Accept deviating bound shapes iff the plan never constrains
        the deviating axes: re-trace the script function at the bound
        shapes and require an instruction stream identical up to leaf
        renaming — same ops, attrs, dtypes, connectivity, and (for
        zero-input generators, whose output shape is baked into their
        kernel) the same shapes. Interior value shapes may differ: they
        derive from the inputs, and every non-generator kernel is
        shape-polymorphic. Verdicts are memoized per shape tuple."""
        verdict = self._shape_verdicts.get(shapes)
        if verdict is None and shapes in self._shape_verdicts:
            return  # previously accepted
        if verdict is None:
            verdict = self._probe_shapes(shapes)
            self._shape_verdicts[shapes] = verdict
        if verdict is not None:
            raise ValueError(verdict)

    def _probe_shapes(self, shapes: tuple) -> Optional[str]:
        declared = tuple(self._arg_shapes)
        try:
            leaves = [
                input_tensor(f"arg{i}", np.zeros(s, dtype=d), sparsity=sp)
                for i, (s, d, sp) in enumerate(
                    zip(shapes, self._arg_dtypes, self._arg_sparsities))]
            outs = self._fn(*leaves)
            if isinstance(outs, LTensor):
                outs = [outs]
            probe = compile_plan(
                list(outs), reuse_enabled=self.runtime.cache is not None,
                opt_level=self.runtime.opt_level)
        except Exception as e:
            return (f"PreparedScript: bound shapes {shapes} != declared "
                    f"{declared} and re-tracing at the bound shapes "
                    f"failed ({type(e).__name__}: {e})")
        reject = (f"PreparedScript: bound shapes {shapes} deviate from "
                  f"the declared {declared} along axes the plan "
                  "constrains (generator shapes, slice bounds, or "
                  "shape-dependent rewrites differ)")
        a_ins, b_ins = self.plan.instructions, probe.instructions
        if len(a_ins) != len(b_ins):
            return reject
        # positional uid correspondence: declared-plan uid -> probe uid
        pair: dict[int, int] = {
            la.node.uid: lb.node.uid
            for la, lb in zip(self._leaves, leaves)}
        for ia, ib in zip(a_ins, b_ins):
            na, nb = ia.node, ib.node
            if (na.op != nb.op or na.attrs != nb.attrs
                    or na.dtype != nb.dtype
                    or len(ia.input_ids) != len(ib.input_ids)):
                return reject
            if not na.inputs and na.shape != nb.shape:
                return reject  # generator output shape is kernel-baked
            for ua, ub in zip(ia.input_ids, ib.input_ids):
                if pair.setdefault(ua, ub) != ub:
                    return reject
            if pair.setdefault(ia.out_id, ib.out_id) != ib.out_id:
                return reject
        for ua, ub in zip(self.plan.output_ids, probe.output_ids):
            if pair.get(ua) != ub:
                return reject
        return None

    def __call__(self, *arrays) -> list[np.ndarray]:
        arrays = self.validate_args(arrays)
        leaf_values: dict[int, Any] = {}
        leaf_lineage: dict[int, str] = {}
        # content fingerprints keep reuse sound across re-binds, but they
        # cost a hash pass per input — only lineage consumers (a reuse
        # cache) need them
        need_lineage = self.runtime.cache is not None
        for leaf, arr in zip(self._leaves, arrays):
            leaf_values[leaf.node.uid] = arr
            if need_lineage:
                leaf_lineage[leaf.node.uid] = \
                    f"{leaf.node.attr('name')}:{_fingerprint(arr)}"
        return self.runtime.run_plan(self.plan, leaf_values, leaf_lineage)

    # ------------------------------------------------------------------
    def prepare_batched(self):
        """Compile the serving form of this script: the same function
        traced over *batched* request leaves, returning a
        `batching.BatchedPlan` replayable at any batch size through
        `LineageRuntime.replay_batch`. This is the deploy-time entry
        `repro.serving.ModelServer` AOT-warms its power-of-two vmap
        buckets from — request compile cost moves fully off the
        request path."""
        from .batching import compile_serving
        return compile_serving(
            self._fn, self._arg_shapes, self._arg_dtypes,
            self._arg_sparsities,
            reuse_enabled=self.runtime.cache is not None,
            opt_level=self.runtime.opt_level)


# ---------------------------------------------------------------------------
# Lineage trace export (§4.1 — debugging / versioning over lineage)
# ---------------------------------------------------------------------------

def lineage_trace(x: LTensor) -> str:
    """Serialize the lineage DAG in a SystemDS-log-like text format."""
    lines: list[str] = []
    seen: dict[int, int] = {}

    def rec(n: Node) -> int:
        if n.uid in seen:
            return seen[n.uid]
        args = [rec(i) for i in n.inputs]
        idx = len(lines)
        seen[n.uid] = idx
        if n.op == "input":
            lid = LEAVES.lineage.get(n.uid, f"input:{n.attr('name')}")
            lines.append(f"({idx}) L·input {lid}")
        elif n.op == "literal":
            lines.append(f"({idx}) L·lit {n.attr('value')}")
        else:
            attrs = {k: v for k, v in n.attrs if k != "index"}
            ref = " ".join(f"({a})" for a in args)
            lines.append(f"({idx}) L·{n.op} {ref} {attrs or ''}".rstrip())
        return idx

    rec(x.node)
    return "\n".join(lines)

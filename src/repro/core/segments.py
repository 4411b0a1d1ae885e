"""Segmentation pass: instruction stream -> maximal fusable segments.

The devito-DLE-style lowering stage of the compiler (SystemDS codegen
analogue): the topologically ordered instruction list produced by
`compile_plan` is partitioned into *segments*, each of which lowers to
one pure Python closure over the `repro.core.backend` kernel registry
and is compiled once by `jax.jit` (see `repro.core.jit_cache`), so XLA
fuses the whole segment and replay skips per-op dispatch entirely.

Segment boundaries are forced by:

  * reuse-probe points — with an active `ReuseCache`, instructions whose
    compile-time cost estimate clears the cache's worth-keeping
    threshold (`Instruction.probe`, see `repro.core.costmodel`) end
    their segment so the probed value stays observable; everything
    between probes fuses, so HPO/CV loops run multi-instruction
    segments with reuse hit behaviour identical to the per-instruction
    interpreter (which gates its probes on the same flag)
  * execution-target changes — heavy `local`, `distributed`, and
    `federated` instructions never share a segment (scalar generators
    are target-neutral and join either side). Placement-aware
    segmentation falls out of this: a federated plan interleaves
    jit-fused local segments with single-instruction `federated`
    segments, and each `fed_*` instruction's *per-site* work is itself
    compiled through the kernel registry + jit cache as per-site
    sub-segments (`repro.core.federated.LocalSite.execute`)
  * shard-exec flips — instructions lowered for the device mesh
    (`placement='sharded'` values, `shard_*` reduces, `reshard`
    boundaries) never share a segment with legacy memory-based
    `distributed` instructions; a maximal shard-exec run lowers to ONE
    `shard_map`-wrapped closure (`build_sharded_segment_fn`), so the
    whole chain — elementwise riders, per-shard partial reduce, psum —
    fuses into a single collective-carrying executable
  * chunked-target runs — instructions lowered for out-of-core
    streaming (`placement='chunked'` prefixes and the `chunk_*` partial
    aggregates) group into `chunked` segments via the ordinary
    target-change rule; the runtime dispatches one warm executable per
    row chunk and sums the partials, with the `combine` boundary (a
    local instruction) closing the streaming scope
  * non-traceable ops — anything in `backend.NON_TRACEABLE_OPS` (the
    `fed_*` site-orchestration ops, `collect` exchange boundaries, and
    host ops like `quantile`) runs in its own segment, outside any jit
    trace; the runtime executes those eagerly on the host path
  * config-variance flips — for batched plans (`repro.core.batching`),
    instructions whose value carries the batch axis (`variant_uids`)
    never share a segment with config-invariant ones: the invariant
    prefix compiles to ordinary executables (shared with single-config
    plans via the jit cache) and is computed ONCE per grid, while
    variant segments are wrapped in `jax.vmap` by the runtime

Each segment carries a *canonical structural key*: `dag.structural_key`
computed with segment inputs pre-seeded positionally, so two segments
that perform the same computation hash identically even when their node
uids differ. `PreparedScript` re-invocations and HPO/CV loops therefore
hit warm compiled executables in the global jit cache instead of
re-tracing.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import TYPE_CHECKING, Optional

from . import backend
from .dag import Node, structural_key
from .tracing import segment_fn_name

if TYPE_CHECKING:  # avoid circular import; Plan imports this lazily
    from .compiler import Instruction, Plan


@dataclass
class Segment:
    """A maximal fusable run of instructions."""

    index: int
    instructions: list
    input_uids: tuple[int, ...]   # external values read (leaves or earlier
                                  # segment outputs), first-use order
    output_uids: tuple[int, ...]  # values that must be observable outside
                                  # (plan outputs + cross-segment uses)
    output_nodes: tuple[Node, ...]
    frees: tuple[int, ...]        # uids dead after this segment
    target: str                   # 'local' | 'distributed' | 'federated'
    key: str                      # canonical structural hash
    variant: bool = False         # carries the config batch axis (vmapped)
    sharded: bool = False         # shard-exec lane: lowered via shard_map
                                  # over the device mesh's data axis
    chunked: bool = False         # streaming lane: the runtime dispatches
                                  # this executable once per row chunk and
                                  # sums the partial aggregates

    @property
    def fused(self) -> bool:
        return len(self.instructions) > 1

    def donatable_positions(self) -> tuple[int, ...]:
        """Argument positions that are donation *candidates*: inputs
        whose uid this segment frees — i.e. it is their compile-time
        last consumer, so after dispatch nothing in the plan can read
        them again. The structural half of the `donate_argnums`
        decision; the runtime intersects it with run-time ownership
        (only buffers produced by traced execution this run and not
        referenced by the reuse cache may actually be donated — see
        `LineageRuntime._donation_mask`).
        """
        dead = set(self.frees)
        return tuple(i for i, u in enumerate(self.input_uids)
                     if u in dead)

    def summary(self) -> str:
        """One-line human identity of this segment — used by the fault
        policy's degradation warnings and `CompileFailedError` context,
        where the structural hash alone tells an operator nothing."""
        ops = [ins.node.op for ins in self.instructions]
        shown = ",".join(ops[:6]) + (",…" if len(ops) > 6 else "")
        lanes = "".join(tag for flag, tag in
                        ((self.variant, "+vmap"), (self.sharded, "+shard"),
                         (self.chunked, "+chunk")) if flag)
        return (f"segment#{self.index}[{self.target}{lanes}] "
                f"ops={shown} ins={len(self.instructions)}")


def _target_neutral(ins) -> bool:
    """Scalar generators (literals, folded constants) cost nothing on any
    target; letting them join either side keeps heavy runs contiguous."""
    return not ins.input_ids and ins.node.shape == ()


def _shard_exec(ins) -> bool:
    """Instruction executes on the device mesh (inside `shard_map`):
    either its value keeps the row-sharded placement or it is one of the
    explicit shard-exec ops (per-shard reduce + psum, reshard)."""
    return (ins.node.placement == "sharded"
            or ins.node.op in backend.SHARD_EXEC_OPS)


def _segment_key(instructions, input_uids, output_positions,
                 target: str) -> str:
    """Uid-independent structural hash of the segment's computation.

    External inputs are seeded into the `structural_key` memo by
    position, truncating recursion at the segment boundary; interior
    nodes (including generators/literals) hash by op/attrs/shape/dtype.
    `output_positions` (indices of exported instructions) must be part
    of the key: two segments with identical bodies but different output
    sets compile to different executables. Input shapes/dtypes are
    deliberately excluded — the jit cache adds the concrete argument
    signature at lookup time.
    """
    memo = {uid: f"@in{i}" for i, uid in enumerate(input_uids)}
    body = ";".join(structural_key(ins.node, memo) for ins in instructions)
    outs = ",".join(str(p) for p in output_positions)
    return hashlib.sha1(
        f"seg1|{target}|{body}|outs={outs}".encode()).hexdigest()


def segment_plan(plan: "Plan", reuse_active: bool,
                 variant_uids: Optional[frozenset[int]] = None
                 ) -> list[Segment]:
    """Partition `plan.instructions` into segments (pure, static).

    `variant_uids` (batched plans only) forces boundaries where the
    config-variance of adjacent instructions differs — target-neutral
    scalar generators still join either side; inside a vmapped segment
    they trace unbatched, so letting them ride along costs nothing."""
    def is_var(ins) -> bool:
        return variant_uids is not None and ins.out_id in variant_uids

    groups: list[list] = []
    group_targets: list[str] = []
    group_variant: list[bool] = []
    group_sharded: list[bool] = []
    cur_target: Optional[str] = None  # None while the group is all-neutral
    cur_variant: Optional[bool] = None
    cur_sharded: Optional[bool] = None
    for ins in plan.instructions:
        neutral = _target_neutral(ins)
        start_new = (
            not groups
            # a probe point must be segment-final so its value is
            # observable for cache probe/put: break after it — except in
            # the chunked lane, where the streaming executor probes and
            # populates every probe-flagged segment OUTPUT itself (a
            # break there would force the chunked prefix to materialize
            # between two streaming scopes, defeating out-of-core)
            or (reuse_active and groups[-1][-1].probe
                and groups[-1][-1].target != "chunked")
            or groups[-1][-1].node.op in backend.NON_TRACEABLE_OPS
            or ins.node.op in backend.NON_TRACEABLE_OPS
            or (not neutral and cur_target is not None
                and ins.target != cur_target)
            or (not neutral and cur_variant is not None
                and is_var(ins) != cur_variant)
            # shard-exec instructions never fuse with legacy memory-based
            # 'distributed' instructions: the former lower via shard_map,
            # the latter via plain jit over big arrays
            or (not neutral and cur_sharded is not None
                and _shard_exec(ins) != cur_sharded))
        if start_new:
            groups.append([ins])
            group_targets.append(ins.target)
            group_variant.append(is_var(ins))
            group_sharded.append(_shard_exec(ins))
            cur_target = None if neutral else ins.target
            cur_variant = None if neutral else is_var(ins)
            cur_sharded = None if neutral else _shard_exec(ins)
        else:
            groups[-1].append(ins)
            if not neutral and cur_target is None:
                cur_target = ins.target
                group_targets[-1] = ins.target
            if not neutral and cur_variant is None:
                cur_variant = is_var(ins)
            if not neutral and cur_sharded is None:
                cur_sharded = _shard_exec(ins)
                group_sharded[-1] = _shard_exec(ins)
            if is_var(ins):
                group_variant[-1] = True

    consumer_segs: dict[int, set[int]] = {}
    for si, group in enumerate(groups):
        for ins in group:
            for uid in ins.input_ids:
                consumer_segs.setdefault(uid, set()).add(si)

    out_ids = set(plan.output_ids)
    segments: list[Segment] = []
    for si, group in enumerate(groups):
        in_group = {ins.out_id for ins in group}
        input_uids: list[int] = []
        seen_in: set[int] = set()
        for ins in group:
            for uid in ins.input_ids:
                if uid not in in_group and uid not in seen_in:
                    seen_in.add(uid)
                    input_uids.append(uid)
        consumed_elsewhere = {uid for uid, segs in consumer_segs.items()
                              if segs - {si}}
        output_uids, output_nodes, output_positions = [], [], []
        for pos, ins in enumerate(group):
            if ins.out_id in out_ids or ins.out_id in consumed_elsewhere:
                output_uids.append(ins.out_id)
                output_nodes.append(ins.node)
                output_positions.append(pos)
        frees: list[int] = []
        seen_f: set[int] = set()
        for ins in group:
            for uid in ins.last_use_of:
                # purely segment-internal values never materialize in the
                # runtime environment, so freeing them is a no-op; only
                # report frees of externally visible values
                if uid in in_group and uid not in output_uids:
                    continue
                if uid not in seen_f:
                    seen_f.add(uid)
                    frees.append(uid)
        segments.append(Segment(
            index=si, instructions=list(group),
            input_uids=tuple(input_uids),
            output_uids=tuple(output_uids),
            output_nodes=tuple(output_nodes),
            frees=tuple(frees),
            target=group_targets[si],
            key=_segment_key(group, input_uids, output_positions,
                             group_targets[si]
                             + ("+sh" if group_sharded[si] else "")),
            variant=group_variant[si],
            sharded=group_sharded[si],
            # target-change boundaries already isolate the streaming
            # lane; the flag routes the group to the streaming executor
            chunked=group_targets[si] == "chunked"))
    return segments


def build_segment_fn(seg: Segment, formats: Optional[dict] = None,
                     drop_output: Optional[int] = None,
                     unshard: bool = False):
    """Lower a segment to one pure closure over the kernel registry.

    The result takes the segment's external inputs positionally (order of
    `seg.input_uids`) and returns the tuple of `seg.output_uids` values.
    Kernel variants are selected from the compile-time format assignment
    (`formats`: uid -> 'dense'|'bcoo'); BCOO values flow through the
    trace as pytrees, so the closure is jit-traceable whenever every
    kernel in the segment is.

    `drop_output` builds the *compensation* variant used on a reuse-cache
    hit in a multi-output segment: the given uid (the probe-final value,
    served from the cache) is removed from the outputs and every
    instruction not needed for the remaining ones is dead-code
    eliminated — the closure computes exactly what the per-instruction
    interpreter would after the same hit.

    `unshard` builds the local-equivalent variant of a sharded segment
    (mesh unavailable at runtime): shard-exec kernels are swapped for
    their single-device base ops (`backend.SHARD_BASE_OPS`; `reshard`
    becomes identity), so the closure computes the same global values
    without any collective.
    """
    fmts = formats or {}
    out_uids = tuple(u for u in seg.output_uids if u != drop_output)
    instructions = seg.instructions
    if drop_output is not None:
        needed = set(out_uids)
        keep = []
        for ins in reversed(seg.instructions):
            if ins.out_id in needed:
                keep.append(ins)
                needed.update(ins.input_ids)
        instructions = keep[::-1]
    steps = [(ins.out_id, ins.input_ids,
              backend.kernel_for_node(
                  ins.node,
                  in_fmts=tuple(fmts.get(u, backend.DENSE)
                                for u in ins.input_ids),
                  out_fmt=fmts.get(ins.out_id, backend.DENSE),
                  unshard=unshard))
             for ins in instructions]
    in_pos = {uid: i for i, uid in enumerate(seg.input_uids)}

    def run(*args):
        env: dict[int, object] = {}
        for out_id, input_ids, kern in steps:
            env[out_id] = kern(*[env[u] if u in env else args[in_pos[u]]
                                 for u in input_ids])
        return tuple(env[u] for u in out_uids)

    # jit names the XLA module after the closure (`jit_ds_add_solve`),
    # and vmap/shard_map keep the name: the device trace then says
    # which segment ran. The jit-cache key does not read it.
    run.__name__ = run.__qualname__ = segment_fn_name(
        ins.node.op for ins in instructions)
    return run


def build_batched_segment_fn(seg: Segment, formats: Optional[dict],
                             batched_uids: frozenset,
                             drop_output: Optional[int] = None):
    """Lower a config-variant segment to one `jax.vmap`-wrapped closure.

    Inputs carrying the batch axis (batched leaves and earlier variant
    segment outputs — `batched_uids`) map over axis 0; config-invariant
    inputs broadcast (`in_axes=None`), so the prefix's gram/xtv is
    traced once and shared across the whole batch inside the executable.
    Outputs mirror the same split. The result is jit-compiled through
    the ordinary jit cache (with a vmap-tagged key, see the runtime).
    """
    import jax
    fn = build_segment_fn(seg, formats, drop_output=drop_output)
    out_uids = tuple(u for u in seg.output_uids if u != drop_output)
    in_axes = tuple(0 if u in batched_uids else None
                    for u in seg.input_uids)
    out_axes = tuple(0 if u in batched_uids else None for u in out_uids)
    return jax.vmap(fn, in_axes=in_axes, out_axes=out_axes)


def shard_specs(seg: Segment) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Per-boundary shard_map specs of a sharded segment.

    Returns ('s'/'r' tag per external input, same per output). Input
    tags come from the consumers' compile-time `sin` attrs (written by
    `compiler.lower_distributed`): 's' = split on the mesh's data axis
    (leading dim), 'r' = replicated. Untouched inputs (only consumed by
    ops without a `sin`, e.g. literals feeding a neutral rider) default
    to replicated. Output tags follow the value's placement: a
    `sharded` output leaves the segment still row-split; everything
    else (psum-reduced values, reshard results) is replicated.
    """
    tags: dict[int, str] = {}
    for ins in seg.instructions:
        sin = ins.node.attr("sin")
        if not sin:
            continue
        for uid, tag in zip(ins.input_ids, sin):
            prev = tags.setdefault(uid, tag)
            if prev != tag:
                raise ValueError(
                    f"conflicting shard specs for value %{uid} in "
                    f"segment {seg.index}: {prev!r} vs {tag!r}")
    in_tags = tuple(tags.get(u, "r") for u in seg.input_uids)
    out_tags = tuple("s" if n.placement == "sharded" else "r"
                     for n in seg.output_nodes)
    return in_tags, out_tags


def build_sharded_segment_fn(seg: Segment, formats: Optional[dict],
                             mesh, drop_output: Optional[int] = None):
    """Lower a shard-exec segment to one `shard_map`-wrapped closure.

    The segment body is the ordinary fused closure; `shard_map` runs it
    per device along the mesh's `data` axis with in/out specs derived
    from the compile-time `sin` tags ('s' -> rows split on the data
    axis, 'r' -> replicated). Collectives (`jax.lax.psum` inside the
    shard-reduce kernels, `all_gather` inside `reshard`) are the only
    cross-shard communication — exactly the exchanges the cost model
    priced when it accepted the lowering. `check_vma=False`: psum
    outputs are replicated by construction.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.distributed.mesh import DATA_AXIS
    fn = build_segment_fn(seg, formats, drop_output=drop_output)
    in_tags, out_tags = shard_specs(seg)
    if drop_output is not None:
        out_tags = tuple(t for u, t in zip(seg.output_uids, out_tags)
                         if u != drop_output)
    in_specs = tuple(P(DATA_AXIS) if t == "s" else P() for t in in_tags)
    out_specs = tuple(P(DATA_AXIS) if t == "s" else P() for t in out_tags)
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def build_config_sharded_segment_fn(seg: Segment, formats: Optional[dict],
                                    batched_uids: frozenset, mesh,
                                    drop_output: Optional[int] = None):
    """Lower a config-variant segment to shard_map-over-`config` around
    the vmapped closure: the bucket axis is split across the mesh's
    `config` axis (each device vmaps over bucket/c configs), while
    config-invariant inputs broadcast replicated. No collectives — the
    configs are embarrassingly parallel; the stacked outputs reassemble
    along axis 0 via the out specs.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.distributed.mesh import CONFIG_AXIS
    fn = build_batched_segment_fn(seg, formats, batched_uids,
                                  drop_output=drop_output)
    out_uids = tuple(u for u in seg.output_uids if u != drop_output)
    in_specs = tuple(P(CONFIG_AXIS) if u in batched_uids else P()
                     for u in seg.input_uids)
    out_specs = tuple(P(CONFIG_AXIS) if u in batched_uids else P()
                      for u in out_uids)
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)

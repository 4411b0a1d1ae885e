"""collective_ms.dp4: device milliseconds of the collective op events in
a whole traced job, on the device with the most. The cell's only
collectives are the psums that sum the per-shard X^T X and X^T y over
the mesh (no reshard runs), which XLA emits as an all-reduce: a plain
`all-reduce`, the asynchronous pair `all-reduce-start` / `-done`, or a
reduce-scatter with an all-gather. An event is an `XLA Ops` event whose
instruction name carries one of those op names."""
import re

PATTERN = re.compile(r"all-reduce|reduce-scatter|all-gather")


def read(ctx):
    if ctx.traced != "job":
        return None
    per_device = [sum(e.dur_ns for e in evs
                      if PATTERN.search(ctx.tracemod.op_name(e.name)))
                  for evs in ctx.trace.ops.values()]
    if not per_device or max(per_device) <= 0:
        return None
    return max(per_device) / 1e6

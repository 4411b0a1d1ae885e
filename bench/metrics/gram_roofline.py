"""gram_roofline: the least time the chip could take for the traced
jobs' grams X^T X, over the device time of the gram kernel's events.

The least time of one gram over X (n x d) is the larger of its
operations, n*d*(d+1) (the symmetric half), at the bf16 peak and its
bytes, X read once as float32 (4*n*d), at the HBM peak; at the paper's
shape the operations bind. It counts the algorithm's work over the
whole X, not the padded tiles or row buckets the kernel runs. The grams
a job needs are the job module's `gram_shapes` (`jobs/<job>.py`).
"""
import re

# the Pallas gram kernel's custom-call, named after its jitted wrapper
# (`kernels/gram/kernel.gram_pallas`); one event per streamed bucket
PATTERN = re.compile(r"^gram_pallas\b")


def least_s(n: int, d: int, peaks: dict) -> tuple[float, str]:
    t_ops = n * d * (d + 1) / peaks["bf16_flops_per_s"]
    t_bytes = 4 * n * d / peaks["hbm_bytes_per_s"]
    return (t_ops, "operations") if t_ops >= t_bytes else (t_bytes, "bytes")


def read(ctx):
    # only a trace of whole jobs holds every bucket of their grams
    if not ctx.peaks or not ctx.traced_jobs or ctx.traced != "job":
        return None
    t_kernel = ctx.tracemod.matching_s(ctx.trace, PATTERN)
    if t_kernel <= 0:
        return None
    drv = ctx.driver
    t_min = sum(least_s(n, d, ctx.peaks)[0] for n, d in
                drv.job_mod.gram_shapes(drv.rows, drv.cols, ctx.traffic))
    return 100.0 * t_min * len(ctx.traced_jobs) / t_kernel

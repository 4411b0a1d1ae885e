"""job_mfu: the operations the window's lifecycle jobs require, over
the window's host-clock seconds (first job's start to last job's end),
as a share of the chips' bf16 peak. The operations of one job are the
job module's `flops` (`jobs/<job>.py`): the algorithm's work, not what
the program repeats or pads."""


def read(ctx):
    if not ctx.peaks or not ctx.jobs or ctx.jobs_s <= 0:
        return None
    drv = ctx.driver
    flops = drv.job_mod.flops(drv.rows, drv.cols, ctx.traffic)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops * len(ctx.jobs) / ctx.jobs_s / peak

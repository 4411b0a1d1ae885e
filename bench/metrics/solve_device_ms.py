"""solve_device_ms: device milliseconds per run of the program that
holds the float64 solve, in a whole traced job: the `XLA Modules`
events whose name carries `solve` (segment programs are named after
their ops, `jit_ds_combine_add_solve`), summed over devices, over their
count."""


def read(ctx):
    if ctx.traced != "job":
        return None
    runs = [e for evs in ctx.trace.modules.values() for e in evs
            if "solve" in e.name]
    if not runs:
        return None
    return sum(e.dur_ns for e in runs) / 1e6 / len(runs)

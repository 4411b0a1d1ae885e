"""plan_compile_share: host seconds the window's jobs spent declaring
and compiling plans, over those jobs' own seconds: the program's
`ds.parfor.build`, `ds.parfor.plan` and `ds.compile_plan` spans
(`RuntimeStats.spans`, one fresh runtime per job). The window runs
without the profiler, so this is the share in the timed jobs."""

SPANS = ("ds.parfor.build", "ds.parfor.plan", "ds.compile_plan")


def read(ctx):
    tables = [(j.get("counters") or {}).get("spans") for j in ctx.jobs]
    job_s = sum(j["t1"] - j["t0"] for j in ctx.jobs)
    if not tables or None in tables or job_s <= 0 \
            or not any(k in t for t in tables for k in SPANS):
        return None
    return 100.0 * sum(t[k]["s"] for t in tables for k in SPANS
                       if k in t) / job_s

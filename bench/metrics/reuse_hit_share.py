"""reuse_hit_share: lineage-reuse hits over probes of the window's jobs
(`ReuseCache.stats`, one fresh cache per job)."""


def read(ctx):
    reuse = [j["counters"]["reuse"] for j in ctx.jobs
             if j.get("counters") and "reuse" in j["counters"]]
    probes = sum(r["probes"] for r in reuse)
    if not probes:
        return None
    return 100.0 * sum(r["hits"] for r in reuse) / probes

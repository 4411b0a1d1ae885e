"""upload_hit_share: calls served by a leaf's resident device copy over
all calls that asked the reuse cache for one (`ReuseCache.stats`
upload_hits / (uploads + upload_hits)) over the window's jobs."""


def read(ctx):
    reuse = [j["counters"]["reuse"] for j in ctx.jobs
             if j.get("counters") and "uploads" in j["counters"].get(
                 "reuse", {})]
    asked = sum(r["uploads"] + r["upload_hits"] for r in reuse)
    if not asked:
        return None
    return 100.0 * sum(r["upload_hits"] for r in reuse) / asked

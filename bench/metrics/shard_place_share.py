"""shard_place_share: host seconds the window's jobs spent placing the
host operands of sharded segments on the mesh, until each copy was
ready, over those jobs' own seconds: the program's `ds.shard.place`
span. Against a program without the span it returns nothing."""

SPANS = ("ds.shard.place",)


def read(ctx):
    tables = [(j.get("counters") or {}).get("spans") for j in ctx.jobs]
    job_s = sum(j["t1"] - j["t0"] for j in ctx.jobs)
    if not tables or None in tables or job_s <= 0 \
            or not any(k in t for t in tables for k in SPANS):
        return None
    return 100.0 * sum(t[k]["s"] for t in tables for k in SPANS
                       if k in t) / job_s

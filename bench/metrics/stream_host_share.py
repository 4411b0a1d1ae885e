"""stream_host_share: main-thread host seconds of the streaming lane in
the window's jobs, over those jobs' own seconds: the program's
`ds.stream.fingerprint`, `ds.stream.dispatch` (the bucket executable's
call with its host-to-device copy) and `ds.stream.combine` spans.
`ds.stream.prep` runs on the prefetch worker, overlapped, and is left
out."""

SPANS = ("ds.stream.fingerprint", "ds.stream.dispatch", "ds.stream.combine")


def read(ctx):
    tables = [(j.get("counters") or {}).get("spans") for j in ctx.jobs]
    job_s = sum(j["t1"] - j["t0"] for j in ctx.jobs)
    if not tables or None in tables or job_s <= 0 \
            or not any(k in t for t in tables for k in SPANS):
        return None
    return 100.0 * sum(t[k]["s"] for t in tables for k in SPANS
                       if k in t) / job_s

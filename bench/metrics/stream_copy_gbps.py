"""stream_copy_gbps: the rate at which the streaming lane copied X's row
buckets to the device in the window's jobs: their streamed bytes
(`streaming.bytes_streamed`) over the seconds of the program's
`ds.stream.copy` span, in which a prefetch worker copies each bucket
and waits until the copy is ready. The seconds of copies in flight at
once add up, so this is the rate of one copy, not of the lane. Read
over the jobs that have the span; against a program without it the
metric is left out."""

SPAN = "ds.stream.copy"


def read(ctx):
    jobs = [j["counters"] for j in ctx.jobs
            if SPAN in ((j.get("counters") or {}).get("spans") or {})]
    copy_s = sum(c["spans"][SPAN]["s"] for c in jobs)
    if copy_s <= 0:
        return None
    streamed = sum(c.get("streaming", {}).get("bytes_streamed", 0)
                   for c in jobs)
    return streamed / copy_s / 1e9

"""device_wait_share: host seconds the window's jobs spent blocked on
device results (plan roots, each streamed bucket's partials, batched
outputs), over those jobs' own seconds: the program's `ds.wait` span."""

SPANS = ("ds.wait",)


def read(ctx):
    tables = [(j.get("counters") or {}).get("spans") for j in ctx.jobs]
    job_s = sum(j["t1"] - j["t0"] for j in ctx.jobs)
    if not tables or None in tables or job_s <= 0 \
            or not any(k in t for t in tables for k in SPANS):
        return None
    return 100.0 * sum(t[k]["s"] for t in tables for k in SPANS
                       if k in t) / job_s

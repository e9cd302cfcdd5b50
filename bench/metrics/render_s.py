"""Seconds of rasterization per job (``BGVResult.timings["render_s"]``), averaged over the window's jobs."""


def read(ctx):
    jobs = ctx.get("jobs") or []
    if not jobs:
        return None
    return sum(j["render_s"] for j in jobs) / len(jobs)

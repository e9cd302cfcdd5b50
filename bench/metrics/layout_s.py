"""Seconds of the supergraph ForceAtlas2 layout per job (``BGVResult.timings["layout_s"]``), averaged over the window's jobs."""


def read(ctx):
    jobs = ctx.get("jobs") or []
    if not jobs:
        return None
    return sum(j["layout_s"] for j in jobs) / len(jobs)

"""Seconds of CMS sizing, superedge aggregation and modularity per job (``StreamStats.stage_seconds["supergraph_s"]``), averaged over the window's jobs."""


def read(ctx):
    jobs = ctx.get("jobs") or []
    if not jobs:
        return None
    return sum(j["supergraph_s"] for j in jobs) / len(jobs)

"""Seconds a job waited on staging copies before refilling a host buffer (``StreamStats.copy_stall_s``), averaged over the window's jobs."""


def read(ctx):
    jobs = ctx.get("jobs") or []
    if not jobs:
        return None
    return sum(j["copy_stall_s"] for j in jobs) / len(jobs)

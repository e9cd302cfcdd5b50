"""Seconds of SCoDA detection per job (``StreamStats.stage_seconds["detect_s"]``, ends in ``block_until_ready``), averaged over the window's jobs."""


def read(ctx):
    jobs = ctx.get("jobs") or []
    if not jobs:
        return None
    return sum(j["detect_s"] for j in jobs) / len(jobs)

"""GB one device contributes to all-reduces and all-gathers per job: the
sum of the program's ``shard.collective_bytes.<stage>`` gauges (computed
from shapes at dispatch), averaged over the window's jobs."""


def read(ctx):
    jobs = [j for j in ctx.get("jobs") or [] if j.get("collective_bytes")]
    if not jobs:
        return None
    return sum(sum(j["collective_bytes"].values()) for j in jobs) / len(jobs) / 1e9

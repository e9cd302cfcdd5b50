"""Share of its roofline the superedge merge kernel reached in the traced
window, in percent (work: ``bench/roofline/merge_scatter_combine.py``)."""

from bench import harness


def read(ctx):
    return harness.roofline_share(ctx, "merge_scatter_combine")

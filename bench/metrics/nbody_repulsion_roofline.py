"""Share of its roofline the FA2 repulsion kernel reached in the traced
window, in percent (work: ``bench/roofline/nbody_repulsion.py``)."""

from bench import harness


def read(ctx):
    return harness.roofline_share(ctx, "nbody_repulsion")

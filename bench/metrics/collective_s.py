"""Device seconds per job in which a collective operation (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, with their
async start and done parts) ran, averaged over the device planes of the
window's trace (``sharded_batch`` driver's ``layer_context``)."""


def read(ctx):
    return ctx.get("collective_s")

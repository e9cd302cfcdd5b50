"""Work of the sorted merge-and-combine of superedges over a window.

Per streamed chunk the algorithm reads the live state run (``before``
pairs) and the chunk's distinct pairs, 12 bytes each (two int32 ids and
a float32 weight), and writes the merged run (``after`` pairs); it does
no floating-point work beyond one add per repeated pair, counted as
flops. Padding of either run is not counted.
"""


def work(ctx):
    w = ctx.get("work")
    if not w:
        return 0.0, 0.0
    nbytes = flops = 0.0
    for before, distinct, after in w["merge_runs"]:
        nbytes += 12.0 * (before + distinct + after)
        flops += before + distinct - after
    return flops * w["jobs"], nbytes * w["jobs"]

"""Work of the exact FA2 repulsion over a window.

Each iteration evaluates every ordered pair of live supernodes, i ≠ j:
the displacement (2), its squared length (3), the distance floor and
square root (2), the radius shift and floor (3), ``kr·m_i·m_j/(d'·d)``
(4) and the two force accumulations (4): 18 flops a pair. It reads the
positions, masses and radii once per tile of rows; that traffic is small
beside the pair work and is counted as 16 bytes per node per iteration.
"""

FLOPS_PER_PAIR = 18.0


def work(ctx):
    w = ctx.get("work")
    if not w:
        return 0.0, 0.0
    n, it = w["n_supernodes"], w["iterations"]
    flops = FLOPS_PER_PAIR * n * (n - 1) * it
    return flops * w["jobs"], 16.0 * n * it * w["jobs"]

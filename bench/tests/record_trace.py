#!/usr/bin/env python3
"""Record the small TPU trace that ``test_tracereduce.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Runs two Pallas kernels of the main path (superedge merge, FA2
repulsion) at small sizes under benchmark annotations, with host-only
pauses between them, inside a ``bench.window`` annotation, and writes the
profiler trace under ``out_dir``. Prints each plane with its lines and
their first events, for reading the trace's layout by eye.
"""
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(out_dir: str) -> int:
    from repro.kernels.merge.ops import merge_combine
    from repro.kernels.repulsion.ops import repulsion

    s_cap, cap = 1 << 10, 1 << 12
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(0, s_cap * s_cap, cap))
    a, b = keys // s_cap, keys % s_cap
    keep = a < b
    a, b = a[keep], b[keep]
    pad = cap - len(a)
    sa = jnp.asarray(np.concatenate([a, np.full(pad, s_cap)]).astype(np.int32))
    sb = jnp.asarray(np.concatenate([b, np.full(pad, s_cap)]).astype(np.int32))
    sw = jnp.asarray(np.concatenate([np.ones(len(a)), np.zeros(pad)]).astype(np.float32))
    pos = jnp.asarray(rng.uniform(-100, 100, (1024, 2)).astype(np.float32))
    mass = jnp.ones(1024, jnp.float32)

    def merge():
        return merge_combine(sa, sb, sw, sa, sb, sw, s_cap)

    def rep():
        return repulsion(pos, mass, 80.0, radii=jnp.sqrt(mass))

    jax.block_until_ready(merge())
    jax.block_until_ready(rep())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("job.pipeline"):
                jax.block_until_ready(merge())
            with jax.profiler.TraceAnnotation("job.png"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("job.render"):
                jax.block_until_ready(rep())
    jax.profiler.stop_trace()

    from jax.profiler import ProfileData

    path = sorted(Path(out_dir).rglob("*.xplane.pb"))[-1]
    print(f"trace {path} ({path.stat().st_size} bytes)")
    for plane in ProfileData.from_file(str(path)).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                stats = dict(ev.stats)
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      str(stats)[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

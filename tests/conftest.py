import os
import sys

# Tests are documented to run with PYTHONPATH=src; this makes them robust
# without it. Do NOT set XLA_FLAGS here — smoke tests must see 1 device;
# the sharded tests force their device count in a subprocess.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The property tests use hypothesis (a dev-extra dependency — CI installs it
# via `pip install -e .[dev]`). Environments without it still run the whole
# suite through this minimal deterministic stand-in: @given replays a fixed
# spread of examples per strategy instead of searching. Only the API surface
# the suite uses (given / settings / strategies.integers / strategies.floats)
# is provided.
try:
    import hypothesis  # noqa: F401
except ImportError:  # pragma: no cover
    import random
    import types

    class _Integers:
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def examples(self, rng, k):
            vals = [self.lo, self.hi, (self.lo + self.hi) // 2]
            vals += [rng.randint(self.lo, self.hi) for _ in range(max(0, k - 3))]
            return vals[:k]

    class _Floats:
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def examples(self, rng, k):
            vals = [self.lo, self.hi, 0.5 * (self.lo + self.hi)]
            vals += [rng.uniform(self.lo, self.hi) for _ in range(max(0, k - 3))]
            return vals[:k]

    def _given(*strategies):
        def deco(fn):
            # Zero-arg wrapper: pytest must not mistake strategy-filled
            # parameters for fixtures.
            def run():
                rng = random.Random(fn.__qualname__)
                cols = [s.examples(rng, 5) for s in strategies]
                for args in zip(*cols):
                    fn(*args)

            run.__name__ = fn.__name__
            run.__doc__ = fn.__doc__
            run.__module__ = fn.__module__
            return run

        return deco

    def _settings(**_kwargs):
        return lambda fn: fn

    _stub = types.ModuleType("hypothesis")
    _strategies = types.ModuleType("hypothesis.strategies")
    _strategies.integers = lambda lo, hi: _Integers(lo, hi)
    _strategies.floats = lambda lo, hi, **_kw: _Floats(lo, hi)
    _stub.given = _given
    _stub.settings = _settings
    _stub.strategies = _strategies
    _stub.__is_repro_stub__ = True
    sys.modules["hypothesis"] = _stub
    sys.modules["hypothesis.strategies"] = _strategies

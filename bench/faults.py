"""Faults a batch cell can have, planted in the timed path.

Each fault takes ``patch``, a callable ``(target, name, value)`` such as
pytest's ``monkeypatch.setattr`` or ``Patch.setattr`` below, and breaks
the program through it. The CPU tests plant them at a tiny size;
``bench/control.py --faults`` plants them at a cell's own size.
"""
from __future__ import annotations

import numpy as np


def scoda_step_returns_state(patch):
    import repro.core.stream as stream

    patch(stream, "scoda_update", lambda state, chunk, thr, cfg: state)


def half_the_chunks_left_out(patch):
    from repro.core.stream import EdgeChunkStream

    real = EdgeChunkStream.device_chunks

    def every_other(self, *a, **k):
        for i, chunk in enumerate(real(self, *a, **k)):
            if i % 2 == 0:
                yield chunk

    patch(EdgeChunkStream, "device_chunks", every_other)


def one_label_altered(patch):
    import repro

    real = repro.biggraphvis

    def altered(*a, **k):
        res = real(*a, **k)
        res.labels = np.asarray(res.labels).copy()
        res.labels[7] = (res.labels[7] + 1) % max(res.n_supernodes, 2)
        return res

    patch(repro, "biggraphvis", altered)


def layout_returns_start(patch):
    import repro.core.forceatlas2 as fa2

    patch(fa2, "_layout_jit", lambda edges, w, m, n, cfg, pos0: (
        pos0, np.zeros((cfg.iterations, 3), np.float32), cfg.iterations))


BATCH = {f.__name__: f for f in (scoda_step_returns_state, half_the_chunks_left_out,
                                 one_label_altered, layout_returns_start)}


class Patch:
    """Attributes set through ``setattr`` until ``undo``."""

    def __init__(self):
        self._saved = []

    def setattr(self, target, name, value):
        self._saved.append((target, name, getattr(target, name)))
        setattr(target, name, value)

    def undo(self):
        while self._saved:
            target, name, value = self._saved.pop()
            setattr(target, name, value)

"""The window's arithmetic: a batch window closes on whole jobs."""
import time

import pytest

from bench import harness


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_batch_window_closes_at_end_of_first_job_past_seconds(monkeypatch):
    batch = harness.load_module("drivers", "batch")
    clock = _Clock()
    monkeypatch.setattr(time, "perf_counter", clock)
    drv = batch.Driver({}, {}, {}, 0)
    durations = iter([4.0, 4.0, 4.0, 4.0])

    def job():
        clock.t += next(durations)
        return {"digest": "d", "stages": {"detect_s": 1.0}}

    drv._job = job
    out = drv.window(10.0)
    assert len(drv.jobs) == 3  # 4 s, 8 s, then 12 s ≥ 10 s closes it
    assert out["job_s"] == pytest.approx(4.0)
    assert drv.attempted == 3 and drv.failed == 0


def test_batch_window_counts_a_failed_job(monkeypatch):
    batch = harness.load_module("drivers", "batch")
    drv = batch.Driver({}, {}, {}, 0)

    def job():
        raise RuntimeError("device lost")

    drv._job = job
    out = drv.window(1.0)
    assert out["job_s"] is None
    assert drv.attempted == 1 and drv.failed == 1 and drv.errors

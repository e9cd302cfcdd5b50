"""Whole runs at a tiny size on the CPU, the look for a chip skipped: a
sound run is correct, and each fault the cells can have, planted in the
timed path, and both controls (the reference with all its lowered parts,
or the layout alone, one precision lower in the program's place) come out
not correct under the cells' own limits."""
import json

import pytest

from bench import faults, harness
from bench.tests import tiny


def run_cell(run, capsys, cell, seed=5, trace=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "4",
                   "--trace", str(trace)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["berkstan.batch", "webgoogle.batch"])
def test_sound_run_is_correct(cell, monkeypatch, tmp_path, capsys):
    run = tiny.use(monkeypatch, tmp_path)
    res = run_cell(run, capsys, cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("fault", sorted(faults.BATCH))
def test_batch_fault_is_not_correct(fault, monkeypatch, tmp_path, capsys):
    run = tiny.use(monkeypatch, tmp_path)
    faults.BATCH[fault](monkeypatch.setattr)
    res = run_cell(run, capsys, "berkstan.batch")
    assert res["correct"] is False, res["checks"]


def test_batch_control_is_not_correct(monkeypatch, tmp_path):
    tiny.use(monkeypatch, tmp_path)
    wl = harness.workload("berkstan.batch")
    cfg = harness.config("berkstan")
    drv = harness.load_module("drivers", "batch").Driver(
        wl, cfg, harness.traffic("batch"), 5)
    drv.setup(warm=False)
    drv.window(0)
    limits = harness.limits("berkstan.batch")
    got = drv.readings()
    sound = got["sound"]
    assert all(sound[k] <= limits[k] for k in limits), sound
    for control in (got["control"], got["control_layout"]):
        assert any(control[k] > limits[k] for k in limits), control
    assert got["control_layout"]["extent_gap"] > limits["extent_gap"]

